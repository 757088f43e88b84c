"""Compile / cache: seconds of backend compilation (``jax.monitoring``)
during set-up; near 0 on a run served from the persistent cache."""


def read(run):
    return run.clock.backend_seconds(before=run.t_setup_done)
