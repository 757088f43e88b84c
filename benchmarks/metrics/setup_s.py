"""Process start to the first request of the window: imports, weights made
on the device, programs compiled or read from the cache, one warm-up of each
shape."""


def read(run):
    return run.t_setup_done - run.t_process
