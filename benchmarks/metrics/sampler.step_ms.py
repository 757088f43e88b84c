"""Sampler: device time of the operations inside the sampling loop, per
denoising step executed in the traced window, in ms (mean over devices).

The loop is the launched program's: an operation is in it where its
instruction belongs to a ``while``'s body or condition in the compiled
text of its module (``lib/launched.py:program_loops``,
``lib/trace.py:mark_loops``), by nesting under the trace's ``while`` event
only where no text exists; a trace whose loop does not add up to the
traced calls' steps is not read (``lib/trace.py:incomplete``)."""

from benchmarks.lib import trace as T


def read(run):
    tr = run.trace_data
    recs = run.traced_records()
    if tr is None or not recs:
        return None
    lo, hi = run.trace_window
    ns = sum(o.dur for o in T.leaf_ops(tr, lo, hi) if o.loop)
    steps = run.work_of(recs)["steps"]
    if not ns or not steps:
        return None
    return ns / len(tr.devices) / steps / 1e6
