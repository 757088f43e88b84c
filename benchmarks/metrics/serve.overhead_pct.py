"""Serve engine: share of the traced window's wall time that is not inside a
dispatch's run: 100 (1 - sum of the dispatches' ``run_ms`` / wall seconds),
over the requests that began and ended while the profiler ran. Batch
assembly, prompt encoding, hand-offs, record building and whatever else the
single-threaded loop does between dispatches; the engine's own clock does
not see it (PERF.md, Findings)."""

from benchmarks.lib.records import dispatches


def read(run):
    recs = run.traced_records()
    batches = dispatches(recs)
    if not batches:
        return None
    wall = max(r["t_end"] for r in recs) - min(r["t_start"] for r in recs)
    inside = sum(b[3] for b in batches.values()) / 1e3
    return 100.0 * (1.0 - inside / wall)
