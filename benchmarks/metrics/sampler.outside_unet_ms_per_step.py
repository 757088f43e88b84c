"""Sampler: device time a denoising step spends outside the U-Net, in ms: the
scan body's own scopes (``sampler/cfg``, ``sampler/scheduler_step``,
``sampler/controller_step``) plus the loop's operations that the scope index
places nowhere (the scan's counters and slices, copies the compiler added).
With the four ``model.*_ms_per_step`` it sums to ``sampler.step_ms``
(``lib/scopes.py``)."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("outside_unet") if scoped else None
