"""Model: device time of the operations of the sampling programs that lie
outside the sampling loop (the VAE / VQ decode and the uint8 conversion), per
image decoded in the traced window, in ms. A sampling program is one with an
operation in the loop.

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
    ops = T.leaf_ops(tr, lo, hi)
    sampling = {o.module for o in ops if o.loop}
    ns = sum(o.dur for o in ops if not o.loop and o.module in sampling)
    images = sum(r["images"] for r in recs)
    if not ns or not images:
        return None
    return ns / len(tr.devices) / images / 1e6
