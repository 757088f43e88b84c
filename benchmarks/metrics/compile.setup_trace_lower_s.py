"""Compile / cache: seconds of set-up in which JAX traced a function to a
jaxpr or lowered one to MLIR, before every compile or cache read (the union
of the ``trace`` and ``lower`` rows of the program's compile ledger, so that
a function traced inside another counts once)."""

from benchmarks.lib import scopes
from benchmarks.lib.trace import union_ns


def read(run):
    rows = scopes.ledger_rows(run, "trace", "lower")
    if rows is None:
        return None
    return union_ns(((r.ended_at - r.seconds, r.ended_at) for r in rows),
                    run.t_process, run.t_setup_done)
