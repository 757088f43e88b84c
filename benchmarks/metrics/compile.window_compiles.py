"""Compile / cache: backend compiles of a second or more between the start
and the end of the window; 0 is expected."""


def read(run):
    return run.clock.compiles_between(*run.window)
