"""Kernels: roofline share of the library flash attention, which runs the
self-attention sites from 1,024 keys up that no controller touches (ten
events a step in both cells since PR 30). For every kernel event inside the
sampling loop the least time the chip could take for softmax(Q K^T) V at the
event's own shape (batch, heads, pixels, head size: the larger of
4 B H P^2 d operations over the bf16 peak and of q, k, v read and the output
written once over the bandwidth), summed, over the summed device time of
those events.
Compute-bound at SD-1.4's shapes: 21.5 GFLOP against 21 MB a row.

Keyed on the Mosaic kernel's events because the TPU's trace carries no scope
names: it reads nothing in a cell whose untouched sites take XLA's own
attention (sites under the 1,024 keys from which the program switches to
the kernel), and would read nothing if another implementation took the
kernel's place. ``model.self_attn_kernel_ms_per_step`` is the per-scope form
of its denominator; keying this reader on the scope is an open question of
PERF.md.

The loop is the launched program's: an operation is in it where its
instruction belongs to a ``while``'s body or condition in the compiled
text of its module (``lib/launched.py:program_loops``,
``lib/trace.py:mark_loops``), by nesting under the trace's ``while`` event
only where no text exists; a trace whose loop does not add up to the
traced calls' steps is not read (``lib/trace.py:incomplete``)."""

from benchmarks.lib import trace as T
from benchmarks.lib.peaks import peaks_for


def read(run):
    tr = run.trace_data
    if not run.on_chip or tr is None:
        return None
    lo, hi = run.trace_window
    peaks = peaks_for(run.device["kind"])
    least = spent = 0.0
    for o in T.leaf_ops(tr, lo, hi):
        if not (T.is_flash_kernel(o) and o.loop and len(o.shape) == 4):
            continue
        b, h, p, d = o.shape
        ops, moved = 4 * b * h * p * p * d, 4 * b * h * p * d * 4
        least += max(ops / peaks["flops_per_s"], moved / peaks["bytes_per_s"])
        spent += o.dur / 1e9
    return 100.0 * least / spent if spent else None
