"""The collector watch: every collection of Python's cyclic garbage collector,
timed on ``time.monotonic()``, the clock of the compile ledger
(``utils.cache.CompileLedger``), of ``obs.spans`` and of the benchmark's
harness.

It starts with the compile ledger, once per process (:func:`start`, called by
``utils.cache.compile_ledger``), so ``CompileLedger.started_at`` is when both
began. Tracing leaves millions of long-lived objects, and the collections
they cost during set-up are on no other clock.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import List, NamedTuple, Optional


class GcRow(NamedTuple):
    generation: int
    start: float       # time.monotonic()
    end: float
    collected: int


class CollectorWatch:
    """Every collection, timed from a ``gc.callbacks`` hook: bounded rows
    (:class:`GcRow`, the compile ledger's capacity), and the counters
    ``gc_collections_total{generation}`` and ``gc_pause_ms{generation}`` of
    the process registry.

    A collection of generation 1 or 2 also runs inside a
    ``jax.profiler.TraceAnnotation`` named ``gc.gen<g>``, so that a device
    trace names the collector where the host stalls on one. One of a
    millisecond or more goes into the span ring as a completed ``gc.collect``
    event (``obs.spans.completed``) with no parent: it is nobody's child, so
    no span's self time loses it. Generation 0 collects thousands of times
    while JAX traces, each in microseconds: that path stays two clock reads,
    a tuple append and two counter adds, opens no annotation, and leaves the
    ring's 4,096 events to the spans the readers need.

    The hook takes no lock: a collection can start inside any allocation,
    the registry's own included, so the counters' children are bound here
    once and ``obs.spans.completed`` touches only the ring."""

    ANNOTATED_FROM = 1          # generation
    RING_FROM_S = 1e-3

    def __init__(self):
        import jax

        from ..utils.cache import CompileLedger
        from . import metrics as metrics_mod
        from . import spans as spans_mod

        self._rows: deque = deque(maxlen=CompileLedger.CAPACITY)
        reg = metrics_mod.registry()
        count = reg.counter("gc_collections_total",
                            "cyclic garbage collections by generation",
                            labels=("generation",))
        pause = reg.counter("gc_pause_ms", "ms the process spent in cyclic "
                            "garbage collections, by generation",
                            labels=("generation",))
        self._count = [count.labels(generation=g) for g in range(3)]
        self._pause = [pause.labels(generation=g) for g in range(3)]
        self._profiler = jax.profiler
        self._completed = spans_mod.completed
        self._t0 = 0.0
        self._open = None

    def _on_gc(self, phase: str, info: dict) -> None:
        g = info["generation"]
        if phase == "start":
            if g >= self.ANNOTATED_FROM:
                self._open = self._profiler.TraceAnnotation(f"gc.gen{g}")
                self._open.__enter__()
            self._t0 = time.monotonic()
            return
        t1 = time.monotonic()
        t0 = self._t0
        self._rows.append(GcRow(g, t0, t1, info["collected"]))
        self._count[g].inc()
        self._pause[g].inc((t1 - t0) * 1e3)
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if t1 - t0 >= self.RING_FROM_S:
            self._completed("gc.collect", int(t0 * 1e9), int(t1 * 1e9),
                            generation=g, collected=info["collected"])

    def rows(self, since: float = float("-inf"),
             before: float = float("inf")) -> List[GcRow]:
        """Collections that ended in ``(since, before]``."""
        return [r for r in list(self._rows) if since < r.end <= before]


_watch: Optional[CollectorWatch] = None


def start() -> CollectorWatch:
    """The process's watch, hooked into ``gc.callbacks`` on the first call."""
    global _watch
    if _watch is None:
        _watch = CollectorWatch()
        gc.callbacks.append(_watch._on_gc)
    return _watch


def collector_watch() -> CollectorWatch:
    """The process's watch, started with the compile ledger."""
    from ..utils.cache import compile_ledger

    compile_ledger()
    return start()
