"""Set-up, instant by instant: what the program's compile ledger, its
collector watch and its entry spans say of ``[t_process, t_setup_done]``.

Every instant of set-up goes to the first of these classes that covers it,
so that the eight sum to ``setup_s``:

1. ``compile.setup_trace_lower_s``: a ``trace`` or ``lower`` row of the
   compile ledger (``p2p_tpu/utils/cache.py:CompileLedger``);
2. ``setup.cache_key_s``: a ``cache_hit`` row outside its ``cache_read``:
   the cache key (serializing and hashing the module) and JAX's bookkeeping;
3. ``setup.cache_read_s``: a ``cache_read`` row, the entry read and loaded;
4. ``setup.uncached_compile_s``: a ``backend`` or ``cache_miss`` row, a
   program compiled although the cache is on;
5. ``setup.gc_s``: a collection of the cyclic garbage collector (the
   collector watch, ``p2p_tpu/obs/collector.py``), the harness's own
   ``gc.collect()`` before ``gc.freeze()`` among them;
6. ``setup.entry_host_s``: an ``entry.*`` or ``sampler.*`` span of the
   program's span ring (``p2p_tpu/obs/spans.py``): the entry points' own
   host work;
7. ``setup.before_program_s``: before ``CompileLedger.started_at``, the
   first instant the program is in charge (imports and the backend's start);
8. ``setup.outside_program_s``: the rest (the harness, the weight fill's
   execution, the warm-up calls' device time and their landing).

Classes 2-4 are ``compile.setup_compile_s`` from the same JAX events, less
what of them falls inside a trace or a lowering. The split is made once a
run and printed to stderr with the programs of class 4 by name.

A program without ``CompileLedger.started_at``, the ``cache_read`` kind, the
collector watch or spans on the harness's clock (any tree before them), and
every run off the chip, reads None: the result line then leaves the metrics
out.
"""

from __future__ import annotations

import sys

from . import scopes
from .trace import union_ns

CLASSES = ("trace_lower", "cache_key", "cache_read", "uncached_compile", "gc",
           "entry_host", "before_program", "outside_program")
_NS = 1e9


def split(lo: float, hi: float, covers) -> list:
    """Seconds of ``[lo, hi]`` in each class: ``covers`` holds one list of
    ``(start, end)`` intervals a class but the last, every instant goes to
    the first class whose intervals cover it, and the last class takes what
    none covers. On whole nanoseconds, so the classes sum to ``hi - lo`` to
    the nanosecond and none is negative."""
    lo_ns, hi_ns = round(lo * _NS), round(hi * _NS)
    events = []
    for k, intervals in enumerate(covers):
        for s, e in intervals:
            s, e = max(round(s * _NS), lo_ns), min(round(e * _NS), hi_ns)
            if e > s:
                events += ((s, 1, k), (e, -1, k))
    events.sort()
    open_ = [0] * len(covers)
    out = [0] * (len(covers) + 1)
    at = lo_ns
    for t, step, k in events:
        if t > at:
            out[next((i for i, n in enumerate(open_) if n), len(covers))] += t - at
            at = t
        open_[k] += step
    out[-1] += hi_ns - at
    return [v / _NS for v in out]


def minus(intervals, holes) -> list:
    """``intervals`` with every ``(start, end)`` of ``holes`` cut out."""
    holes = sorted(holes)
    out = []
    for s, e in intervals:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append((s, hs))
            s = max(s, he)
        if e > s:
            out.append((s, e))
    return out


def _program(run):
    """``(ledger, watch)`` of a program that keeps both and the split's
    kinds; None where it does not or the run is not on the chip."""
    if not run.on_chip:
        return None
    try:
        from p2p_tpu.obs import collector
        from p2p_tpu.utils.cache import compile_ledger
    except ImportError:
        return None
    ledger = compile_ledger()
    if (getattr(ledger, "started_at", None) is None
            or "cache_read" not in getattr(ledger, "KINDS", ())):
        return None
    return ledger, collector.collector_watch()


def load(run):
    """``{class: seconds}`` of the run's set-up, made once; None where the
    program or the run offers no split."""
    if "_setup_parts" not in run.__dict__:
        run._setup_parts = _load(run)
    return run._setup_parts


def part(run, name: str):
    parts = load(run)
    return None if parts is None else parts[name]


def _load(run):
    found = _program(run)
    ring = scopes.ring_spans(run)
    if found is None or ring is None:
        return None
    ledger, watch = found
    lo, hi = run.t_process, run.t_setup_done
    rows = ledger.rows(since=lo, before=hi)

    def spans_of(*kinds):
        return [(r.ended_at - r.seconds, r.ended_at) for r in rows if r.kind in kinds]

    reads = spans_of("cache_read")
    entry = [(s / _NS, e / _NS) for _, _, name, s, e, _ in ring
             if name.startswith(("entry.", "sampler."))]
    covers = [spans_of("trace", "lower"), minus(spans_of("cache_hit"), reads),
              reads, spans_of("backend", "cache_miss"),
              [(r.start, r.end) for r in watch.rows(since=lo, before=hi)],
              entry, [(lo, ledger.started_at)]]
    parts = dict(zip(CLASSES, split(lo, hi, covers)))
    _print(run, parts, rows, watch)
    return parts


def _print(run, parts, rows, watch) -> None:
    import jax

    from p2p_tpu.obs import metrics, spans

    say = lambda *a: print(*a, file=sys.stderr)          # noqa: E731
    lo, hi = run.t_process, run.t_setup_done
    # the seven with class 1 as compile.setup_trace_lower_s reads it
    trace_lower = union_ns(((r.ended_at - r.seconds, r.ended_at) for r in rows
                            if r.kind in ("trace", "lower")), lo, hi)
    seven = sum(v for k, v in parts.items() if k != "trace_lower")
    compile_s = run.clock.backend_seconds(before=hi)
    cache = sum(parts[k] for k in ("cache_key", "cache_read", "uncached_compile"))
    counted = metrics.registry().get("gc_collections_total")
    say("set-up by part (s): " + " ".join(f"{k}:{v:.4f}" for k, v in parts.items())
        + f"; the seven and compile.setup_trace_lower_s {seven + trace_lower:.6f}"
        f" of t_setup_done - t_process {hi - lo:.6f}; cache parts {cache:.4f}"
        f" of compile.setup_compile_s {compile_s:.4f}; collector rows kept"
        f" {len(watch.rows())} of {sum(c.value for _, c in counted.samples()):.0f};"
        f" span ring dropped {spans.recorder().dropped}")
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    asked = {r.name for r in rows if r.kind == "cache_miss"}
    say(f"programs compiled in set-up although the cache is on (seconds name;"
        f" * = under jax_persistent_cache_min_compile_time_secs {floor}; "
        f"- = never offered to the cache): " + (" ".join(
            f"{r.seconds:.3f}:{r.name}{'*' if r.seconds < floor else ''}"
            f"{'' if r.name in asked else '-'}"
            for r in rows if r.kind == "backend") or "none"))
    gc_rows = watch.rows(since=lo, before=hi)
    say("collections in set-up by generation (count seconds): " + " ".join(
        f"gen{g}:{sum(1 for r in gc_rows if r.generation == g)}:"
        f"{sum(r.end - r.start for r in gc_rows if r.generation == g):.4f}"
        for g in range(3)))
