"""Device: the weights the window's programs are handed, in GiB: the bytes of
every ``*_params`` argument of the programs that ran in the traced window
(``Launch.weights_bytes`` of the sampling program and of the text programs,
by part and dtype; a part that several programs take is counted once). It is
what ``peak_hbm_gib`` is mostly made of, and what the width kernels are
stored in moves. A program that keeps no such record gives nothing to
read."""

from benchmarks.lib import launched
from benchmarks.lib import trace as T


def read(run):
    if not run.on_chip or run.trace_data is None:
        return None
    lo, hi = run.trace_window
    parts = {}
    for module in {o.module for o in T.leaf_ops(run.trace_data, lo, hi)}:
        by_part = getattr(launched.newest(module), "weights_bytes", None) or {}
        for part, by_dtype in by_part.items():
            parts[part] = max(parts.get(part, 0), sum(by_dtype.values()))
    return sum(parts.values()) / 2 ** 30 if parts else None
