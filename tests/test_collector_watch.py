"""The collector watch started with the compile ledger (``obs/collector.py``): every
collection of the cyclic garbage collector on ``time.monotonic()``, its
counters, what reaches the span ring and the profiler, and what does not."""

import gc
import time

import pytest

from p2p_tpu.obs import collector
from p2p_tpu.obs import metrics as metrics_mod
from p2p_tpu.obs import spans
from p2p_tpu.utils import cache as cache_mod


def _counter(name, generation):
    return metrics_mod.registry().get(name).labels(generation=generation).value


def test_the_watch_starts_once_with_the_ledger():
    ledger = cache_mod.compile_ledger()
    watch = collector.collector_watch()
    cache_mod.compile_ledger()
    assert collector.collector_watch() is watch and cache_mod.compile_ledger() is ledger
    hooks = [c for c in gc.callbacks if getattr(c, "__self__", None) is watch]
    assert len(hooks) == 1
    assert watch._rows.maxlen == cache_mod.CompileLedger.CAPACITY


def test_a_full_collection_gives_a_row_and_counts():
    watch = collector.collector_watch()
    n0, ms0 = _counter("gc_collections_total", 2), _counter("gc_pause_ms", 2)
    t0 = time.monotonic()
    gc.collect(2)
    t1 = time.monotonic()
    full = [r for r in watch.rows(since=t0) if r.generation == 2]
    assert len(full) == 1
    row = full[0]
    assert t0 <= row.start <= row.end <= t1 and row.collected >= 0
    assert _counter("gc_collections_total", 2) == n0 + 1
    assert _counter("gc_pause_ms", 2) == pytest.approx(
        ms0 + (row.end - row.start) * 1e3)


@pytest.mark.parametrize("seconds,in_ring", [
    (0.0005, False), (0.00099, False), (0.0011, True), (0.25, True)])
def test_only_a_collection_of_a_millisecond_or_more_reaches_the_ring(
        seconds, in_ring, monkeypatch):
    watch = collector.CollectorWatch()
    clock = [50.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    total = spans.recorder().total
    with spans.span("entry.text2image") as sid:
        watch._on_gc("start", {"generation": 0, "collected": 0, "uncollectable": 0})
        clock[0] += seconds
        watch._on_gc("stop", {"generation": 0, "collected": 7, "uncollectable": 0})
        added = spans.events()[total - spans.recorder().total:]
    assert watch.rows() == [collector.GcRow(0, 50.0, 50.0 + seconds, 7)]
    assert [e["name"] for e in added] == (["entry.text2image", "gc.collect"]
                                          if in_ring else ["entry.text2image"])
    if in_ring:
        ev = added[-1]      # nobody's child: no span's self time loses it
        assert ev["event"] == "span_end" and ev["parent"] is None
        assert ev["within"] == sid
        assert ev["dur_ms"] == pytest.approx(seconds * 1e3)
        assert ev["t_ns"] == int((50.0 + seconds) * 1e9)
        assert (ev["generation"], ev["collected"]) == (0, 7)


@pytest.mark.parametrize("generation", (0, 1, 2))
def test_only_older_generations_open_a_profiler_annotation(generation, monkeypatch):
    import jax

    watch = collector.CollectorWatch()
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    info = {"generation": generation, "collected": 0, "uncollectable": 0}
    watch._on_gc("start", info)
    watch._on_gc("stop", info)
    name = f"gc.gen{generation}"
    assert opened == ([] if generation == 0 else [("enter", name), ("exit", name)])
    assert watch._open is None and len(watch.rows()) == 1


def test_ten_thousand_young_collections_evict_no_open_entry_span():
    """A flood of generation-0 collections, as while JAX traces, leaves the
    ring to the spans: the open entry span's start is still there. At the
    ring's default capacity, which a CLI run earlier in the process may have
    changed."""
    collector.collector_watch()
    old = spans.capacity()
    spans.set_capacity(spans.DEFAULT_CAPACITY)
    try:
        with spans.span("entry.prepare") as sid:
            total = spans.recorder().total
            for _ in range(10_000):
                gc.collect(0)
            added = spans.recorder().total - total
            starts = [e for e in spans.events()
                      if e["event"] == "span_start" and e["span"] == sid]
    finally:
        spans.set_capacity(old)
    assert starts and added < 100


def test_a_completed_span_is_one_end_event_and_off_when_spans_are_off():
    total = spans.recorder().total
    spans.completed("gc.collect", 1_000_000, 4_000_000, generation=1)
    ev = spans.events()[-1]
    assert spans.recorder().total == total + 1
    assert (ev["event"], ev["name"], ev["t_ns"], ev["dur_ms"]) == (
        "span_end", "gc.collect", 4_000_000, 3.0)
    spans.set_enabled(False)
    try:
        spans.completed("gc.collect", 0, 5_000_000)
    finally:
        spans.set_enabled(True)
    assert spans.recorder().total == total + 1
