"""The set-up split (``benchmarks/lib/setup_parts.py``) and its seven readers:
the partition on synthetic intervals, the readers on a synthetic run of a
program with a compile ledger, a collector watch and entry spans, against a
program without them, and in the CPU rehearsal of the benchmark with the
seven entries added to its manifest in memory."""

import copy
import os
import random
import sys
import time
from types import SimpleNamespace

import pytest

from benchmarks.lib import harness, setup_parts

SEVEN = ("setup.cache_key_s", "setup.cache_read_s", "setup.uncached_compile_s",
         "setup.gc_s", "setup.entry_host_s", "setup.before_program_s",
         "setup.outside_program_s")
TRACE_LOWER = "compile.setup_trace_lower_s"
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal")


def _read(name, run):
    return harness.load_module("metrics", name).read(run)


# -- the partition ------------------------------------------------------------

@pytest.mark.parametrize("covers,expected", [
    # nested: the first class takes the overlap, the second keeps the rest
    ([[(2, 4)], [(1, 6)]], [2, 3, 5]),
    # the later class first in time: order is by class, never by start
    ([[(5, 7)], [(0, 6)]], [2, 5, 3]),
    # a class that covers what an earlier one does gets none of it
    ([[(0, 5), (4, 8)], [(1, 3), (6, 7)], [(7.5, 9)]], [8, 0, 1, 1]),
    # overlapping rows of one class count once; outside [lo, hi] is cut off
    ([[(-3, 2), (1, 2.5)], [], [(9, 14)]], [2.5, 0, 1, 6.5]),
])
def test_split_takes_overlaps_in_the_stated_order(covers, expected):
    assert setup_parts.split(0.0, 10.0, covers) == pytest.approx(expected)


@pytest.mark.parametrize("seed", (0, 1, 2147483659))
def test_split_sums_to_the_interval_and_leaves_nothing_negative(seed):
    rng = random.Random(seed)
    lo, hi = 1000.0 + rng.random(), 1030.0 + rng.random()
    covers = []
    for _ in range(7):
        rows = []
        for _ in range(rng.randrange(0, 400)):
            s = rng.uniform(lo - 1, hi + 1)
            rows.append((s, s + rng.expovariate(20.0)))
        covers.append(rows)
    parts = setup_parts.split(lo, hi, covers)
    assert len(parts) == 8 and min(parts) >= 0.0
    assert abs(sum(parts) - (hi - lo)) < 1e-9
    # everything covered: nothing is left over
    assert setup_parts.split(lo, hi, [[(lo, hi)]])[-1] == 0.0


def test_minus_cuts_each_hole_out():
    assert setup_parts.minus([(0, 10), (20, 30)], [(2, 3), (9, 21), (25, 26)]) == [
        (0, 2), (3, 9), (21, 25), (26, 30)]
    assert setup_parts.minus([(0, 1)], []) == [(0, 1)]
    assert setup_parts.minus([(0, 1)], [(-1, 2)]) == []


# -- the readers on a synthetic run -------------------------------------------

def _span(sid, name, start, end):
    return {"event": "span_end", "span": sid, "parent": None, "name": name,
            "t_ns": int(end * 1e9), "dur_ms": (end - start) * 1e3}


@pytest.fixture
def program(monkeypatch):
    """A ledger, a collector watch and a ring of one made-up set-up from
    t = 0 to 30 s, the ledger started at 10 s:

    - trace 10-12, lower 12-13 (a young collection at 11-11.2 inside it);
    - a cache hit 13-17 whose read is 13.5-16.5;
    - a program compiled although the cache is on, 17.5-19;
    - a full collection 18.5-19.5;
    - ``entry.text2image`` 12.5-22 and a span of another layer 23-24.
    """
    from p2p_tpu.obs import collector
    from p2p_tpu.utils import cache as cache_mod

    clock = [10.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    ledger = cache_mod.CompileLedger()
    watch = collector.CollectorWatch()
    backend = []

    def at(t, event, secs=None):
        clock[0] = t
        if secs is None:                   # an event without a duration
            ledger._on_event(event)
            return
        ledger._on_duration(event, secs, fun_name="f")
        if event.endswith("backend_compile_duration"):
            backend.append((t, secs))

    at(12.0, "/jax/core/compile/jaxpr_trace_duration", 2.0)
    at(13.0, "/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0)
    at(13.0, "/jax/compilation_cache/compile_requests_use_cache")
    at(13.0, "/jax/compilation_cache/cache_hits")
    at(16.5, "/jax/compilation_cache/cache_retrieval_time_sec", 3.0)
    at(17.0, "/jax/core/compile/backend_compile_duration", 4.0)
    at(17.0, "/jax/compilation_cache/compile_requests_use_cache")
    at(19.0, "/jax/core/compile/backend_compile_duration", 1.5)
    at(40.0, "/jax/core/compile/backend_compile_duration", 9.0)   # after set-up
    for g, t0, t1 in ((0, 11.0, 11.2), (2, 18.5, 19.5)):
        clock[0] = t0
        watch._on_gc("start", {"generation": g, "collected": 0, "uncollectable": 0})
        clock[0] = t1
        watch._on_gc("stop", {"generation": g, "collected": 3, "uncollectable": 0})
    monkeypatch.setattr(cache_mod, "compile_ledger", lambda: ledger)
    monkeypatch.setattr(collector, "collector_watch", lambda: watch)
    clock_of_run = SimpleNamespace(
        backend_seconds=lambda before: sum(s for t, s in backend if t <= before))
    run = SimpleNamespace(
        on_chip=True, t_process=0.0, t_setup_done=30.0, clock=clock_of_run,
        ring_events=[_span(1, "entry.text2image", 12.5, 22.0),
                     _span(2, "serve.batch", 23.0, 24.0)])
    return SimpleNamespace(run=run, ledger=ledger, watch=watch)


EXPECTED = {TRACE_LOWER: 3.0, "setup.cache_key_s": 1.0, "setup.cache_read_s": 3.0,
            "setup.uncached_compile_s": 1.5, "setup.gc_s": 0.5,
            "setup.entry_host_s": 3.0, "setup.before_program_s": 10.0,
            "setup.outside_program_s": 8.0}


def test_the_seven_and_trace_lower_sum_to_set_up(program, capsys):
    run = program.run
    got = {m: _read(m, run) for m in EXPECTED}
    assert got == pytest.approx(EXPECTED)
    assert sum(got.values()) == pytest.approx(run.t_setup_done - run.t_process, abs=1e-9)
    # the cache parts are compile.setup_compile_s from the same events
    cache = sum(got[m] for m in ("setup.cache_key_s", "setup.cache_read_s",
                                 "setup.uncached_compile_s"))
    assert cache == pytest.approx(_read("compile.setup_compile_s", run))
    err = capsys.readouterr().err
    assert err.count("set-up by part (s):") == 1        # split and printed once
    assert "): 1.500:f\n" in err and "gen2:1:1.0000" in err   # asked, over the floor


def test_readers_read_nothing_off_the_chip(program):
    program.run.on_chip = False
    assert {m: _read(m, program.run) for m in SEVEN} == dict.fromkeys(SEVEN)


@pytest.mark.parametrize("older", ("no_started_at", "no_cache_read_kind", "no_watch"))
def test_readers_read_nothing_on_an_older_program(program, monkeypatch, older):
    """The readers also run beside an older program: one whose ledger
    lacks ``started_at`` or the ``cache_read`` kind, or that keeps no
    collector watch, prints none of the seven."""
    if older == "no_started_at":
        del program.ledger.started_at
    elif older == "no_cache_read_kind":
        program.ledger.KINDS = ("trace", "lower", "backend", "cache_hit", "cache_miss")
    else:                                  # no p2p_tpu.obs.collector to import
        import p2p_tpu.obs

        monkeypatch.delattr(p2p_tpu.obs, "collector")
        monkeypatch.setitem(sys.modules, "p2p_tpu.obs.collector", None)
    assert {m: _read(m, program.run) for m in SEVEN} == dict.fromkeys(SEVEN)


# -- the CPU rehearsal, with the seven in its manifest ------------------------

def _rehearsal_manifest():
    """The rehearsal's manifest with the seven entries of the benchmark's
    own, found by name, listed for every toy cell."""
    manifest = copy.deepcopy(harness.load_json(os.path.join(REHEARSAL, "BENCHMARK.json")))
    root = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = [c["name"] for c in manifest["workloads"]]
    for name in SEVEN:
        entry = next(e for e in root["per_layer"] if e["name"] == name)
        manifest["per_layer"].append(dict(entry, workloads=cells))
    return manifest


@pytest.mark.parametrize("cell", ("tiny.edit-replace", "tiny_ldm.edit-batch4",
                                  "tiny.serve-backlog"))
def test_cpu_rehearsal_loads_the_seven_readers(cell, monkeypatch, tmp_path):
    """A traced rehearsal run calls each of the seven readers; none raises,
    and off the chip none prints a number. Its profile goes under
    ``tmp_path``: other test files trace the same toy cells, and two
    processes writing one ``.bench_trace/<cell>`` read each other's files."""
    manifest = _rehearsal_manifest()
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    loaded, load = [], harness.load_module

    def noting(kind, name):
        loaded.append((kind, name))
        return load(kind, name)

    monkeypatch.setattr(harness, "load_module", noting)
    r = harness.run_cell(manifest, cell, 2147483659, 0.2, True, time.monotonic(),
                         require_chip=False, root=REHEARSAL)
    assert r["correct"] is True and r["failed"] == 0
    assert {("metrics", m) for m in SEVEN} <= set(loaded)
    assert not set(r["metrics"]) & set(SEVEN)


def test_cpu_rehearsal_split_sums_to_its_set_up(monkeypatch, capsys):
    """The same run taken as a chip's: on a real set-up (the toy preset's
    programs traced, compiled or read, the harness's collection) the eight
    parts sum to ``setup_s`` and the cache parts are
    ``compile.setup_compile_s`` to 1 %."""
    runs, make = [], harness.Run

    def keeping(**kw):
        runs.append(make(**kw))
        return runs[-1]

    monkeypatch.setattr(harness, "Run", keeping)
    manifest = _rehearsal_manifest()
    t_process = time.monotonic()
    harness.run_cell(manifest, "tiny.edit-replace", 3800000001, 0.2, False,
                     t_process, require_chip=False, root=REHEARSAL)
    run = runs[0]
    run.on_chip = True
    got = {m: _read(m, run) for m in (TRACE_LOWER,) + SEVEN}
    assert min(got.values()) >= 0.0
    assert sum(got.values()) == pytest.approx(_read("setup_s", run), abs=1e-6)
    compile_s = _read("compile.setup_compile_s", run)
    cache = sum(got[m] for m in SEVEN[:3])
    assert compile_s > 0 and cache == pytest.approx(compile_s, rel=0.01)
    assert "set-up by part (s):" in capsys.readouterr().err
