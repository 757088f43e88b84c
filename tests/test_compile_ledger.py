"""The program's compile ledger (``utils/cache.py``): one ``jax.monitoring``
listener, rows on ``time.monotonic()``, nothing per call; a read of the
persistent cache split into JAX's own time of the read and the rest."""

import random
import time

import jax
import jax.numpy as jnp
import pytest

from p2p_tpu.obs import metrics as metrics_mod
from p2p_tpu.utils import cache as cache_mod


@pytest.fixture
def every_program_is_cached():
    """The suite writes only compiles of a second or more to its cache."""
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was)


def _program(salt):
    """A program nobody compiled before, not even the last run of this test
    (``salt`` is drawn anew), as a new function each time it is asked for."""
    def fresh_for_the_ledger(x):
        return jnp.sin(x) * salt + x @ x

    return fresh_for_the_ledger


def _kinds(rows, name="fresh_for_the_ledger"):
    return [r.kind for r in rows if name in r.name]


def test_fresh_jit_second_lowering_and_cached_call(every_program_is_cached):
    ledger = cache_mod.compile_ledger()
    assert cache_mod.compile_ledger() is ledger          # one listener
    salt, x = random.random(), jnp.ones((8, 8))
    counted = metrics_mod.registry().counter(
        "compiles_total", "program builds recorded", labels=("what",))
    hits0 = counted.labels(what="cache_hit").value
    built0 = ledger.programs

    t0 = time.monotonic()
    jax.jit(_program(salt))(x).block_until_ready()
    t1 = time.monotonic()
    first = ledger.rows(since=t0, before=t1)
    assert _kinds(first) == ["trace", "lower", "cache_miss", "backend"]
    assert all(t0 < r.ended_at <= t1 and r.seconds >= 0 for r in first)
    miss, backend = [r for r in first if r.kind in ("cache_miss", "backend")
                     and "fresh_for_the_ledger" in r.name]
    assert miss.seconds == backend.seconds

    # a second, identical lowering: the backend "compile" is a cache read,
    # and JAX's own time of the read is written ahead of it
    jax.jit(_program(salt))(x).block_until_ready()
    second = ledger.rows(since=t1)
    assert _kinds(second) == ["trace", "lower", "cache_read", "cache_hit"]
    assert ledger.programs - built0 >= 2
    assert counted.labels(what="cache_hit").value - hits0 >= 1

    # a cached call: nothing at all
    jitted = jax.jit(_program(salt))
    jitted(x)
    t2 = time.monotonic()
    for _ in range(3):
        jitted(x).block_until_ready()
    assert ledger.rows(since=t2) == []


def test_rows_select_by_kind_and_time():
    ledger = cache_mod.CompileLedger()
    for event, secs in (("/jax/core/compile/jaxpr_trace_duration", 0.5),
                        ("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25),
                        ("/jax/some/other_duration", 9.0)):
        ledger._on_duration(event, secs, fun_name="f")
    # never offered to the cache: a backend row and no miss
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 2.0, fun_name="f")
    ledger._on_event("/jax/compilation_cache/compile_requests_use_cache")
    ledger._on_event("/jax/compilation_cache/cache_hits")
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 0.1, fun_name="g")
    assert [(r.kind, r.seconds, r.name) for r in ledger.rows()] == [
        ("trace", 0.5, "f"), ("lower", 0.25, "f"), ("backend", 2.0, "f"),
        ("cache_hit", 0.1, "g")]
    assert ledger.programs == 2
    assert [r.kind for r in ledger.rows("trace", "lower")] == ["trace", "lower"]
    last = ledger.rows()[-1].ended_at
    assert ledger.rows(since=last) == [] and len(ledger.rows(before=last)) == 4


def test_chip_smoke_clock_reads_the_ledger():
    import chip_smoke

    clock = chip_smoke.CompileClock()
    seconds0, programs0 = clock.mark()
    ledger = cache_mod.compile_ledger()
    ledger._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="f")
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 61.26, fun_name="f")
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 0.2, fun_name="g")
    seconds1, programs1 = clock.mark()
    assert seconds1 - seconds0 == pytest.approx(61.96)
    assert programs1 - programs0 == 1 and clock.programs[programs0:] == [61.3]


def test_a_cache_hit_holds_one_read_no_longer_than_it(every_program_is_cached):
    """Cleared in memory, the program is read back from the persistent
    cache: one ``cache_hit`` row with exactly one ``cache_read`` row inside
    it, under its name; the ledger was listening before either."""
    ledger = cache_mod.compile_ledger()
    salt, x = random.random(), jnp.ones((8, 8))
    t_first = time.monotonic()
    jax.jit(_program(salt))(x).block_until_ready()
    jax.clear_caches()
    t0 = time.monotonic()
    jax.jit(_program(salt))(x).block_until_ready()
    rows = [r for r in ledger.rows(since=t0) if "fresh_for_the_ledger" in r.name]
    hits = [r for r in rows if r.kind == "cache_hit"]
    reads = [r for r in rows if r.kind == "cache_read"]
    assert len(hits) == 1 and len(reads) == 1
    hit, read = hits[0], reads[0]
    assert 0.0 < read.seconds <= hit.seconds
    assert hit.ended_at - hit.seconds <= read.ended_at - read.seconds
    assert read.ended_at <= hit.ended_at
    assert all(ledger.started_at < r.ended_at - r.seconds
               for r in ledger.rows(since=t_first))


def test_a_read_is_written_ahead_of_its_hit_under_its_name(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    ledger = cache_mod.CompileLedger()
    assert ledger.started_at == 100.0 and "cache_read" in ledger.KINDS
    ledger._on_event("/jax/compilation_cache/compile_requests_use_cache")
    ledger._on_event("/jax/compilation_cache/cache_hits")
    clock[0] = 103.0
    ledger._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 2.5)
    assert ledger.rows() == []                    # named by the event that closes it
    clock[0] = 103.25
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 3.0,
                        fun_name="jit_f")
    assert [tuple(r) for r in ledger.rows()] == [
        ("cache_read", 2.5, 103.0, "jit_f"), ("cache_hit", 3.0, 103.25, "jit_f")]
    assert ledger.programs == 1
    # a miss: one row of the compile and one of the miss, on the same instant
    ledger._on_event("/jax/compilation_cache/compile_requests_use_cache")
    clock[0] = 110.0
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 1.5,
                        fun_name="jit_g")
    miss, backend = ledger.rows(since=103.25)
    assert (miss.kind, backend.kind) == ("cache_miss", "backend")
    assert miss[1:] == backend[1:] == (1.5, 110.0, "jit_g")
    assert ledger.rows("cache_read") == ledger.rows("cache_read", before=103.0)


def test_every_kind_the_ledger_writes_is_declared():
    assert set(cache_mod.CompileLedger.KINDS) == {
        "trace", "lower", "backend", "cache_hit", "cache_miss", "cache_read"}
    assert set(cache_mod.CompileLedger._DURATIONS.values()) <= set(
        cache_mod.CompileLedger.KINDS)
