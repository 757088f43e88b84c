"""Fault-tolerance layer: typed failure classification + retry policy,
the dispatch watchdog, the crash-safe journal (WAL + replay, including
corruption), chaos-plan injection through the engine loop, output
validation, graceful degradation, and the disabled-mode parity proof.

Control-flow tests ride the same injected-runner + virtual-timer harness
as tests/test_serve.py, so every retry/backoff/drain decision is asserted
exactly; the disabled-mode proof and the NaN-validation numerics use the
session tiny pipeline.
"""

import json
import time

import numpy as np
import pytest

from p2p_tpu.serve import (
    FaultPlan,
    InjectedFault,
    Journal,
    Request,
    RetryPolicy,
    WatchdogTimeout,
    classify,
    replay,
    serve_forever,
)
from p2p_tpu.serve import faults as faults_mod
from p2p_tpu.serve.engine_loop import TERMINAL_STATUSES, DegradeConfig
from p2p_tpu.serve.journal import TERMINAL_STATUSES as WAL_STATUSES
from tests.test_serve import FakeRunner, VirtualTimer, _by_status, _req


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------


def test_classify_marker_types_win_over_messages():
    assert classify(WatchdogTimeout(100.0)) == "timeout"
    assert classify(InjectedFault("transient")) == "transient"
    assert classify(InjectedFault("fatal")) == "fatal"
    assert classify(InjectedFault("nonsense")) == "poison"
    assert classify(faults_mod.FatalFault("anything at all")) == "fatal"


def test_classify_message_patterns_and_poison_default():
    assert classify(RuntimeError("RESOURCE_EXHAUSTED: hbm oom")) == "transient"
    assert classify(RuntimeError("device busy, try again")) == "transient"
    assert classify(RuntimeError("shape mismatch: (4,) vs (8,)")) == "fatal"
    assert classify(ValueError("checkpoint missing unet/scale")) == "fatal"
    # Fatal patterns outrank transient ones: a structurally-wrong program
    # must never be retried just because the message also says
    # "unavailable".
    assert classify(RuntimeError("checkpoint store unavailable")) == "fatal"
    # Anything unrecognized degrades to the pre-taxonomy behavior.
    assert classify(RuntimeError("novel nonsense")) == "poison"
    assert classify(KeyError("unet")) == "poison"


def test_journal_and_engine_terminal_status_vocabularies_agree():
    assert set(WAL_STATUSES) == set(TERMINAL_STATUSES)


# ---------------------------------------------------------------------------
# Retry policy
# ---------------------------------------------------------------------------


def test_backoff_deterministic_bounded_and_keyed():
    p = RetryPolicy(base_ms=50.0, multiplier=2.0, max_backoff_ms=300.0,
                    jitter_frac=0.25)
    # Pure function of (key, attempt): identical across instances/runs.
    again = RetryPolicy(base_ms=50.0, multiplier=2.0, max_backoff_ms=300.0,
                        jitter_frac=0.25)
    for attempt in range(5):
        assert p.backoff_ms(attempt, "k") == again.backoff_ms(attempt, "k")
    # Distinct keys de-synchronize.
    assert p.backoff_ms(0, "batch:1") != p.backoff_ms(0, "batch:2")
    # Exponential base within [base, base*(1+jitter)], capped.
    for attempt, base in ((0, 50.0), (1, 100.0), (2, 200.0), (3, 300.0),
                          (8, 300.0)):
        b = p.backoff_ms(attempt, "k")
        assert base <= b <= base * 1.25
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)


def test_retry_call_retries_transients_only():
    calls, slept, notified = [], [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("device busy")
        return "served"

    out = faults_mod.retry_call(
        flaky, policy=RetryPolicy(max_attempts=3, base_ms=10.0),
        key="t", sleep=slept.append,
        on_retry=lambda a, d, e: notified.append((a, d)))
    assert out == "served" and len(calls) == 3
    assert len(slept) == 2 and len(notified) == 2
    assert [a for a, _ in notified] == [0, 1]

    # Non-transient: propagates immediately, no sleeps.
    calls.clear(), slept.clear()

    def poisoned():
        calls.append(1)
        raise RuntimeError("novel nonsense")

    with pytest.raises(RuntimeError, match="nonsense"):
        faults_mod.retry_call(poisoned, sleep=slept.append)
    assert len(calls) == 1 and not slept

    # Exhaustion: the last transient failure propagates.
    calls.clear()

    def always_busy():
        calls.append(1)
        raise RuntimeError("device busy")

    with pytest.raises(RuntimeError, match="busy"):
        faults_mod.retry_call(always_busy,
                              policy=RetryPolicy(max_attempts=3),
                              sleep=lambda s: None)
    assert len(calls) == 3


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------


def test_watchdog_returns_result_and_propagates_errors():
    assert faults_mod.run_with_watchdog(lambda: 42, 1000.0) == 42
    with pytest.raises(ValueError, match="boom"):
        faults_mod.run_with_watchdog(
            lambda: (_ for _ in ()).throw(ValueError("boom")), 1000.0)
    with pytest.raises(ValueError, match="positive"):
        faults_mod.run_with_watchdog(lambda: 1, 0.0)


def test_watchdog_shoots_a_hang():
    t0 = time.monotonic()
    with pytest.raises(WatchdogTimeout):
        faults_mod.run_with_watchdog(lambda: time.sleep(2.0), 80.0,
                                     poll_ms=5.0)
    assert time.monotonic() - t0 < 1.5  # did not wait out the sleep


def test_watchdog_heartbeat_rearms_deadline():
    """A slow-but-alive worker (heartbeat advancing) outlives the nominal
    deadline; the watchdog only shoots silence."""
    beats = [0]

    def slow_but_alive():
        for _ in range(6):
            time.sleep(0.05)
            beats[0] += 1
        return "done"

    # 6 * 50ms = 300ms of work against a 120ms deadline: only the
    # heartbeat keeps it alive.
    out = faults_mod.run_with_watchdog(slow_but_alive, 120.0,
                                       heartbeat=lambda: beats[0],
                                       poll_ms=10.0)
    assert out == "done"


def test_progress_watchdog_sink_fires_on_steps_and_traces_nothing():
    """The heartbeat rides the existing step callback: installing the sink
    must not add a single op to a disabled-progress program (the PR 3
    jaxpr-identity discipline extended to the watchdog)."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.utils import progress

    def lowered():
        def f(x):
            def body(c, i):
                progress.emit_step(False, i, phase="phase1")
                return c * 1.5, None
            out, _ = jax.lax.scan(body, x, jnp.arange(3))
            return out
        # The lowered StableHLO, not the compiled text: the latter carries
        # the call site's line numbers in its stack-frame tables.
        return jax.jit(f).lower(jnp.float32(1.0)).as_text()

    base = lowered()
    beats = [0]
    progress.set_watchdog_sink(lambda: beats.__setitem__(0, beats[0] + 1))
    try:
        assert lowered() == base           # sink is host-side only
        assert "custom_call" not in base
        # And when the callback IS traced in, every step beats the sink.
        def g(x):
            def body(c, i):
                progress.emit_step(True, i)
                return c + 1.0, None
            out, _ = jax.lax.scan(body, x, jnp.arange(4))
            return out
        jax.jit(g)(jnp.float32(0.0)).block_until_ready()
        jax.effects_barrier()
        assert beats[0] >= 4
    finally:
        progress.set_watchdog_sink(None)


# ---------------------------------------------------------------------------
# Journal: WAL + replay + corruption
# ---------------------------------------------------------------------------


def _wal_lines(path):
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


def test_journal_roundtrip_and_replay_partitions(tmp_path):
    path = str(tmp_path / "j.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a", "prompt": "x"}, 1.0)
        j.admitted({"request_id": "b", "prompt": "y"}, 2.0)
        j.dispatched(["a", "b"], 1, 3.0)
        j.terminal("a", "ok", 4.0)
        j.event("degrade", level=1)
    rs = replay(path)
    assert rs.pending_ids == ["b"]          # admitted, no terminal
    assert rs.terminal == {"a": "ok"}
    assert rs.skipped_corrupt == 0 and rs.duplicate_terminals == 0
    # Missing file = empty state, never an error.
    empty = replay(str(tmp_path / "nope.wal"))
    assert not empty.pending and not empty.terminal


def test_journal_replay_survives_truncated_and_garbage_tails(tmp_path):
    """The crash-shaped corruption satellite: a torn mid-record tail,
    garbage bytes, and non-object JSON are each skipped with a counter —
    never a crash, and never at the cost of the intact prefix."""
    path = str(tmp_path / "j.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a", "prompt": "x"}, 1.0)
        j.admitted({"request_id": "b", "prompt": "y"}, 2.0)
        j.terminal("a", "ok", 3.0)
    with open(path, "ab") as f:
        f.write(b'{"type": "terminal", "id": "b", "sta')   # torn mid-write
    rs = replay(path)
    assert rs.pending_ids == ["b"]          # b's terminal never landed
    assert rs.skipped_corrupt == 1

    with open(path, "ab") as f:
        f.write(b"\n\x00\xff<<garbage>>\n[1, 2, 3]\n")
    rs = replay(path)
    assert rs.pending_ids == ["b"]
    assert rs.skipped_corrupt == 3          # torn + garbage + non-object


def test_journal_replay_skips_malformed_records_with_counter(tmp_path):
    path = str(tmp_path / "j.wal")
    recs = [
        {"type": "admitted", "request": {"request_id": "a", "prompt": "x"}},
        {"type": "admitted", "request": "not-a-dict"},       # bad shape
        {"type": "admitted", "request": {"prompt": "no id"}},
        {"type": "terminal", "id": "a", "status": "oka"},    # torn status
        {"type": "terminal", "status": "ok"},                # missing id
        {"type": "frobnicate", "id": "a"},                   # unknown type
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rs = replay(path)
    assert rs.pending_ids == ["a"]          # the torn terminal didn't count
    assert rs.skipped_corrupt == 5


def test_journal_replay_collapses_duplicate_terminals(tmp_path):
    """A crash between the terminal append and the fsync can replay one
    terminal line: the first wins, the duplicate is counted, and the id
    stays exactly-once (not pending, not served twice)."""
    path = str(tmp_path / "j.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a", "prompt": "x"}, 1.0)
        j.terminal("a", "ok", 2.0)
        j.terminal("a", "ok", 2.0)
        j.terminal("a", "error", 3.0)       # conflicting dup: first wins
    rs = replay(path)
    assert not rs.pending
    assert rs.terminal == {"a": "ok"}
    assert rs.duplicate_terminals == 2


def test_journal_sync_is_batched_and_durable(tmp_path):
    path = str(tmp_path / "j.wal")
    j = Journal(path)
    j.admitted({"request_id": "a", "prompt": "x"}, 1.0)
    j.sync()
    j.terminal("a", "ok", 2.0)              # appended, not yet synced
    # A reader at the last sync point sees the admitted entry (the
    # unsynced tail may or may not be visible — durability is only
    # promised up to sync()).
    assert any(r["type"] == "admitted" for r in _wal_lines(path))
    j.close()                               # close syncs the tail
    types = [r["type"] for r in _wal_lines(path)]
    assert types == ["admitted", "terminal"]


# ---------------------------------------------------------------------------
# FaultPlan
# ---------------------------------------------------------------------------


def test_fault_plan_validation_and_roundtrip(tmp_path):
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan(by_request={"a": "gremlins"})
    with pytest.raises(ValueError, match="unknown fault-plan field"):
        FaultPlan.from_dict({"by_batch": {}, "surprise": 1})
    plan = FaultPlan(by_batch={3: "transient"}, by_request={"r": "poison"},
                     seed=7)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = FaultPlan.load(path)
    assert loaded.to_dict() == plan.to_dict()
    assert len(loaded) == 2


def test_fault_plan_one_shot_vs_sticky_semantics():
    plan = FaultPlan(by_batch={1: "transient"}, by_request={"v": "nan"})
    f = plan.take(1, ["a", "b"])
    assert f.kind == "transient" and f.rids == ("a", "b")
    assert plan.take(1, ["a", "b"]) is None          # one-shot: spent
    # Sticky nan keeps matching its victim across dispatches.
    for _ in range(3):
        f = plan.take(9, ["x", "v"])
        assert f.kind == "nan" and f.rids == ("v",)
    plan.reset()
    assert plan.take(1, ["a"]).kind == "transient"   # re-armed


def test_fault_plan_generate_is_deterministic():
    rids = [f"r{i}" for i in range(64)]
    a = FaultPlan.generate(3, rids, rate=0.3)
    b = FaultPlan.generate(3, rids, rate=0.3)
    c = FaultPlan.generate(4, rids, rate=0.3)
    assert a.to_dict() == b.to_dict()
    assert a.to_dict() != c.to_dict()
    assert 0 < len(a) < len(rids)
    assert set(a.by_request.values()) <= {"transient", "poison", "nan"}


# ---------------------------------------------------------------------------
# Engine integration: injected faults through the loop
# ---------------------------------------------------------------------------


def _serve(tiny_pipe, reqs, timer=None, runner_cls=FakeRunner, log=None,
           poison=(), **kw):
    timer = timer or VirtualTimer()

    def factory(compile_key, bucket):
        return runner_cls(compile_key, bucket, timer, poison=poison, log=log)

    return list(serve_forever(tiny_pipe, reqs, runner_factory=factory,
                              timer=timer, **kw))


def test_transient_fault_retries_same_batch_to_success(tiny_pipe):
    log = []
    plan = FaultPlan(by_batch={1: "transient"})
    recs = _serve(tiny_pipe, [_req("a"), _req("b")], log=log, chaos=plan,
                  max_batch=2, max_wait_ms=10.0)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["a", "b"]
    # The injected flake fires before the runner executes, so only the
    # retry's successful run reaches it — same batch, same composition.
    assert log == [["a", "b"]]
    s = by["summary"][0]
    assert s["retries"] == 1
    assert s["faults"]["transient"] == 1 and s["faults"]["poison"] == 0
    # The backoff was charged to the virtual clock: total latency exceeds
    # the pure compute time (warm 1000 + run 100) by at least base_ms.
    (a,) = [r for r in by["ok"] if r["request_id"] == "a"]
    assert a["total_ms"] > 1100.0 + RetryPolicy().base_ms


def test_transient_exhaustion_resolves_error_with_budget_reason(tiny_pipe):
    class AlwaysBusy(FakeRunner):
        def __call__(self, entries, guidance):
            raise RuntimeError("RESOURCE_EXHAUSTED: device busy")

    recs = _serve(tiny_pipe, [_req("a")], runner_cls=AlwaysBusy,
                  max_batch=1, max_wait_ms=10.0,
                  retry_policy=RetryPolicy(max_attempts=3, base_ms=10.0))
    by = _by_status(recs)
    (err,) = by["error"]
    assert "persisted through 3 attempts" in err["reason"]
    s = by["summary"][0]
    assert s["retries"] == 2                 # 3 runs = 2 retries
    assert s["faults"]["transient"] == 3


def test_backoff_budget_is_capped_by_the_lane_deadline(tiny_pipe):
    """A transient backoff must never outspend a lane's own deadline: the
    entry expires during the backoff instead of burning another attempt."""
    class AlwaysBusy(FakeRunner):
        def __call__(self, entries, guidance):
            raise RuntimeError("device busy")

    recs = _serve(tiny_pipe, [_req("a", deadline_ms=1200.0)],
                  runner_cls=AlwaysBusy, max_batch=1, max_wait_ms=10.0,
                  retry_policy=RetryPolicy(max_attempts=5, base_ms=500.0))
    by = _by_status(recs)
    (exp,) = by["expired"]
    assert "during transient backoff" in exp["reason"]
    # Far fewer than the 5-attempt budget actually ran.
    assert by["summary"][0]["faults"]["transient"] < 5


def test_chaos_poison_takes_the_isolation_path(tiny_pipe):
    log = []
    plan = FaultPlan(by_request={"r1": "poison"})
    recs = _serve(tiny_pipe, [_req(f"r{i}") for i in range(3)], log=log,
                  chaos=plan, max_batch=4, max_wait_ms=10.0)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["r0", "r2"]
    (err,) = by["error"]
    assert err["request_id"] == "r1" and "injected poison" in err["reason"]
    # Injected faults fire before the runner, so only the survivors'
    # isolated re-runs reach it — the poisoned batch and r1's lone retry
    # both aborted pre-run.
    assert log == [["r0"], ["r2"]]
    assert by["summary"][0]["faults"]["poison"] == 2  # batch + r1 alone


def test_fatal_fault_drains_the_loop_with_terminal_records(tiny_pipe):
    """A fatal classification stops the world cleanly: the failed batch,
    everything still queued, and everything in the batcher all resolve to
    error records, and the summary says why."""
    plan = FaultPlan(by_batch={1: "fatal"})
    # 'waiting' rides a different compile key, so it is in the batcher
    # (not the fatal batch) when the drain happens.
    reqs = [_req("a"), _req("b"), _req("waiting", steps=5)]
    recs = _serve(tiny_pipe, reqs, chaos=plan, max_batch=2,
                  max_wait_ms=10.0)
    by = _by_status(recs)
    assert not by.get("ok")
    statuses = {r["request_id"]: r["reason"] for r in by["error"]}
    assert set(statuses) == {"a", "b", "waiting"}
    assert "fatal" in statuses["a"]
    assert "drained after fatal fault" in statuses["waiting"]
    s = by["summary"][0]
    assert s["faults"]["fatal"] == 1 and "injected fatal" in s["fatal"]


def test_chaos_hang_with_watchdog_times_out_and_quarantines(tiny_pipe):
    plan = FaultPlan(by_batch={1: "hang"})
    recs = _serve(tiny_pipe, [_req("a"), _req("b")], chaos=plan,
                  max_batch=2, max_wait_ms=10.0, watchdog_ms=60.0)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["timeout"]) == ["a", "b"]
    assert all("watchdog" in r["reason"] for r in by["timeout"])
    s = by["summary"][0]
    assert s["watchdog_timeouts"] == 1
    assert s["faults"]["timeout"] == 1
    assert s["program_cache"]["quarantined"] == 1


def test_chaos_nan_converts_to_invalid_output(tiny_pipe):
    plan = FaultPlan(by_request={"bad": "nan"})
    recs = _serve(tiny_pipe, [_req("good"), _req("bad")], chaos=plan,
                  max_batch=2, max_wait_ms=10.0, validate_outputs=True)
    by = _by_status(recs)
    assert [r["request_id"] for r in by["ok"]] == ["good"]
    (inv,) = by["invalid_output"]
    assert inv["request_id"] == "bad" and "NaN" in inv["reason"]
    assert "images" not in inv               # the image is withheld
    # Without validation the same plan ships the lane untouched (the nan
    # injection models bad *numerics*, which only validation can see).
    plan.reset()
    recs = _serve(tiny_pipe, [_req("good"), _req("bad")], chaos=plan,
                  max_batch=2, max_wait_ms=10.0)
    assert sorted(r["request_id"]
                  for r in _by_status(recs)["ok"]) == ["bad", "good"]


def test_real_lane_finite_flags_nan_lanes():
    """The actual jitted finite-check: a poisoned lane flags False without
    touching its batchmates, on the real runner's latents path."""
    from p2p_tpu.engine.sampler import lane_finite

    lats = np.zeros((4, 2, 8, 8, 4), np.float32)
    lats[1, 0, 3, 2, 1] = np.nan
    lats[3, 1, 0, 0, 0] = np.inf
    assert lane_finite(lats).tolist() == [True, False, True, False]


def test_validation_converts_runner_reported_nan_lane(tiny_pipe):
    """End-to-end: a runner whose finite flags mark one lane bad yields
    exactly one invalid_output record and healthy batchmates."""
    class NaNLane(FakeRunner):
        def __call__(self, entries, guidance):
            out = super().__call__(entries, guidance)
            flags = [e.request_id != "bad" for e in entries]
            self.last_lane_finite = np.array(flags)
            return out

    recs = _serve(tiny_pipe, [_req("good"), _req("bad")],
                  runner_cls=NaNLane, max_batch=2, max_wait_ms=10.0,
                  validate_outputs=True)
    by = _by_status(recs)
    assert [r["request_id"] for r in by["ok"]] == ["good"]
    assert [r["request_id"] for r in by["invalid_output"]] == ["bad"]


# ---------------------------------------------------------------------------
# Journal through the engine: crash replay, exactly-once
# ---------------------------------------------------------------------------


def _terminal(recs):
    return [r for r in recs if r.get("status") in TERMINAL_STATUSES]


def test_journal_records_full_request_lifecycle(tiny_pipe, tmp_path):
    path = str(tmp_path / "serve.wal")
    journal = Journal(path)
    recs = _serve(tiny_pipe, [_req("a"), _req("b")], journal=journal,
                  max_batch=2, max_wait_ms=10.0)
    journal.close()
    assert len(_by_status(recs)["ok"]) == 2
    lines = _wal_lines(path)
    kinds = [(l["type"], l.get("id") or
              (l.get("request") or {}).get("request_id") or
              tuple(l.get("ids", []))) for l in lines]
    assert ("admitted", "a") in kinds and ("admitted", "b") in kinds
    assert ("dispatched", ("a", "b")) in kinds
    assert ("terminal", "a") in kinds and ("terminal", "b") in kinds
    # Order: every id admitted before dispatched before terminal.
    assert kinds.index(("admitted", "a")) < kinds.index(
        ("dispatched", ("a", "b"))) < kinds.index(("terminal", "a"))


def test_crash_replay_serves_remaining_exactly_once(tiny_pipe, tmp_path):
    """The ISSUE 4 crash-replay invariant: kill the loop mid-trace,
    restart against the same WAL and the same trace — every request is
    served exactly once across both incarnations, completed requests
    never re-run, and the trace copies of replayed ids dedupe."""
    path = str(tmp_path / "serve.wal")
    reqs = [_req(f"r{i}", arrival=i * 10.0, steps=4 + (i % 3))
            for i in range(8)]

    journal = Journal(path)
    first = []
    gen = _iter_serve(tiny_pipe, reqs, journal)
    for rec in gen:
        first.append(rec)
        if len(_terminal(first)) >= 3:
            break                            # simulated crash
    gen.close()
    journal._f.close()                       # raw close: no final fsync

    journal2 = Journal(path)
    rs = journal2.replay_state
    assert set(rs.terminal) == {r["request_id"] for r in _terminal(first)}
    assert rs.pending                        # admitted-but-unresolved work
    second = list(serve_forever(
        tiny_pipe, reqs, journal=journal2, max_batch=2, max_wait_ms=10.0,
        runner_factory=_fake_factory(), timer=VirtualTimer()))
    journal2.close()

    seen = {}
    for rec in _terminal(first) + _terminal(second):
        assert rec["request_id"] not in seen, \
            f"{rec['request_id']} resolved twice"
        seen[rec["request_id"]] = rec["status"]
    assert set(seen) == {f"r{i}" for i in range(8)}
    assert set(seen.values()) == {"ok"}
    # Replayed requests are flagged, and the second summary owns up to
    # the replay bookkeeping.
    s = _by_status(second)["summary"][0]
    assert s["replay"]["pending"] == len(rs.pending)
    assert s["replay"]["terminal"] == 3
    assert s["replay"]["deduped"] == 8       # every trace copy deduped
    replayed = [r for r in _terminal(second) if r.get("replayed")]
    assert len(replayed) == len(rs.pending)


def _fake_factory(timer=None, **kw):
    timer = timer or VirtualTimer()

    def factory(compile_key, bucket):
        return FakeRunner(compile_key, bucket, timer, **kw)

    return factory


def _iter_serve(tiny_pipe, reqs, journal, **kw):
    timer = VirtualTimer()
    return serve_forever(tiny_pipe, reqs, journal=journal,
                         runner_factory=_fake_factory(timer), timer=timer,
                         max_batch=2, max_wait_ms=10.0, **kw)


def test_crash_replay_survives_corrupt_wal_tail(tiny_pipe, tmp_path):
    """Torn WAL tail + restart: the corrupt line is skipped (counted in
    the summary), the intact prefix drives replay."""
    path = str(tmp_path / "serve.wal")
    journal = Journal(path)
    recs = _serve(tiny_pipe, [_req("a"), _req("b")], journal=journal,
                  max_batch=2, max_wait_ms=10.0)
    assert len(_by_status(recs)["ok"]) == 2
    journal.close()
    with open(path, "ab") as f:
        f.write(b'{"type": "admitted", "request": {"requ')   # torn
    journal2 = Journal(path)
    second = list(serve_forever(
        tiny_pipe, [_req("a"), _req("c", steps=5)], journal=journal2,
        runner_factory=_fake_factory(), timer=VirtualTimer(),
        max_batch=2, max_wait_ms=10.0))
    journal2.close()
    by = _by_status(second)
    # a already terminal: deduped. c is new work.
    assert [r["request_id"] for r in by["ok"]] == ["c"]
    s = by["summary"][0]
    assert s["replay"]["skipped_corrupt"] == 1
    assert s["replay"]["deduped"] == 1


def test_duplicate_id_rejection_is_not_journaled_as_terminal(
        tiny_pipe, tmp_path):
    """A terminal WAL line for a duplicate submission's id would make a
    crash-replay drop the still-live original — the dup rejection is
    recorded to the caller but NOT to the WAL."""
    path = str(tmp_path / "serve.wal")
    journal = Journal(path)
    recs = _serve(tiny_pipe, [_req("a"), _req("a")], journal=journal,
                  max_batch=1, max_wait_ms=10.0)
    journal.close()
    by = _by_status(recs)
    assert len(by["rejected"]) == 1 and len(by["ok"]) == 1
    terminals = [l for l in _wal_lines(path) if l["type"] == "terminal"]
    assert [t["id"] for t in terminals] == ["a"]
    assert terminals[0]["status"] == "ok"


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------


def test_degrade_config_validation():
    with pytest.raises(ValueError, match="depth_threshold"):
        DegradeConfig(depth_threshold=0)
    with pytest.raises(ValueError, match="window_ms"):
        DegradeConfig(window_ms=0.0)
    with pytest.raises(ValueError, match="min_bucket"):
        DegradeConfig(min_bucket=3)


def test_sustained_pressure_degrades_then_sheds_then_recovers(
        tiny_pipe, tmp_path):
    """The full degradation ladder under a synthetic overload: forced
    gate='auto' (level 1), shrunken bucket (level 2), shedding (level 3)
    — then full recovery once the queue drains, with every transition
    journaled."""
    path = str(tmp_path / "serve.wal")
    journal = Journal(path)
    # Distinct compile keys + a huge flush wait: the batcher holds work,
    # so each 30ms arrival is one loop iteration with rising depth; the
    # tail arrivals (50s+) land after the drain and walk the level back.
    reqs = [_req(f"r{i:02d}", arrival=i * 30.0, steps=4 + i)
            for i in range(12)]
    reqs += [_req(f"t{i}", arrival=50_000.0 + i * 200.0, steps=3)
             for i in range(4)]
    recs = _serve(tiny_pipe, reqs, journal=journal, max_batch=4,
                  max_wait_ms=400.0,
                  degrade=DegradeConfig(depth_threshold=2, window_ms=50.0,
                                        min_bucket=1))
    journal.close()
    by = _by_status(recs)
    s = by["summary"][0]
    assert by.get("shed"), "level 3 was never reached"
    for r in by["shed"]:
        assert "load shed at degradation level" in r["reason"]
    # Level 1 forced cheaper sampling on gate-less admissions.
    degraded_ok = [r for r in by["ok"] if r.get("degraded_gate")]
    assert degraded_ok, "no admission was force-gated at level >= 1"
    # Recovery: the tail arrivals walked the level back down.
    events = [l for l in _wal_lines(path) if l["type"] == "event"]
    ups = [e for e in events if e["kind"] == "degrade"]
    downs = [e for e in events if e["kind"] == "restore"]
    assert [e["level"] for e in ups] == [1, 2, 3]
    assert downs and downs[-1]["level"] < 3
    assert s["degrade_transitions"] == len(ups) + len(downs)
    # Exactly-once still holds under shedding.
    seen = [r["request_id"] for r in _terminal(recs)]
    assert sorted(seen) == sorted(r.request_id for r in reqs)


# ---------------------------------------------------------------------------
# ProgramCache: quarantine + build retries
# ---------------------------------------------------------------------------


def test_program_cache_quarantine_is_not_an_eviction():
    from p2p_tpu.serve import ProgramCache

    c = ProgramCache(capacity=4)
    c.get("k", lambda: "prog")
    assert c.quarantine("k") is True
    assert "k" not in c
    assert c.quarantine("k") is False        # already gone: no double count
    stats = c.stats()
    assert stats["quarantined"] == 1 and stats["evictions"] == 0
    # A later miss may rebuild (the hang may have been the device).
    _, hit, _ = c.get("k", lambda: "prog2")
    assert hit is False


def test_program_cache_build_retry_policy():
    from p2p_tpu.serve import ProgramCache

    c = ProgramCache(capacity=4,
                     retry_policy=RetryPolicy(max_attempts=3, base_ms=0.1))
    calls = []

    def flaky_build():
        calls.append(1)
        if len(calls) < 2:
            raise RuntimeError("RESOURCE_EXHAUSTED during compile")
        return "prog"

    runner, hit, _ = c.get("k", flaky_build)
    assert runner == "prog" and hit is False and len(calls) == 2
    assert c.stats()["build_retries"] == 1

    # Non-transient build failures propagate without retry.
    calls.clear()

    def broken_build():
        calls.append(1)
        raise RuntimeError("shape mismatch in checkpoint")

    with pytest.raises(RuntimeError, match="shape mismatch"):
        c.get("k2", broken_build)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# Disabled-mode parity: fault tolerance off == fault tolerance idle
# ---------------------------------------------------------------------------


def test_disabled_mode_parity_records_and_outputs(tiny_pipe, tmp_path):
    """The ISSUE 4 acceptance proof, operationalized: a run with every
    fault-tolerance feature OFF is record-for-record and bit-for-bit
    identical to a run with everything armed but idle (journal on, empty
    chaos plan, generous watchdog, validation on, degradation configured
    but never triggered) — the machinery costs nothing until a fault or
    overload actually happens."""
    reqs = [_req(f"r{i}", arrival=i * 5.0) for i in range(4)]

    base = _serve(tiny_pipe, reqs, max_batch=4, max_wait_ms=10.0)
    journal = Journal(str(tmp_path / "idle.wal"))
    armed = _serve(tiny_pipe, reqs, max_batch=4, max_wait_ms=10.0,
                   journal=journal, chaos=FaultPlan(),
                   watchdog_ms=600_000.0, validate_outputs=True,
                   degrade=DegradeConfig(depth_threshold=64,
                                         window_ms=60_000.0))
    journal.close()

    assert len(base) == len(armed)
    for b, a in zip(base, armed):
        assert b["status"] == a["status"]
        assert b.get("request_id") == a.get("request_id")
        if b["status"] == "ok":
            assert np.array_equal(np.asarray(b["images"]),
                                  np.asarray(a["images"]))
            assert b["batch_id"] == a["batch_id"]
            assert b["batch_lanes"] == a["batch_lanes"]
            assert b["batch_occupancy"] == a["batch_occupancy"]
    sb, sa = base[-1], armed[-1]
    assert sb["counts"] == sa["counts"]
    assert sb["n_batches"] == sa["n_batches"]
    assert sa["retries"] == 0 and sa["degrade_transitions"] == 0
    assert sa["faults"] == {k: 0 for k in sa["faults"]}


def test_disabled_mode_real_pipe_bitwise_with_validation_armed(tiny_pipe):
    """On the real sweep path: arming output validation must not change a
    single pixel — the finite check is a separate program on the sweep's
    output, never a change to the sweep itself."""
    reqs = [_req("v", steps=3)]
    base = list(serve_forever(tiny_pipe, reqs, max_batch=1,
                              max_wait_ms=5.0))
    armed = list(serve_forever(tiny_pipe, reqs, max_batch=1,
                               max_wait_ms=5.0, validate_outputs=True))
    (b,) = [r for r in base if r["status"] == "ok"]
    (a,) = [r for r in armed if r["status"] == "ok"]
    assert np.array_equal(np.asarray(b["images"]), np.asarray(a["images"]))


# ---------------------------------------------------------------------------
# Registry families
# ---------------------------------------------------------------------------


def test_fault_and_replay_metric_families(tiny_pipe, tmp_path):
    from p2p_tpu.obs import metrics as metrics_mod

    reg = metrics_mod.registry()
    reg.reset()
    plan = FaultPlan(by_batch={1: "transient"}, by_request={"p": "poison"})
    path = str(tmp_path / "m.wal")
    journal = Journal(path)
    recs = _serve(tiny_pipe, [_req("a"), _req("p")], journal=journal,
                  chaos=plan, max_batch=2, max_wait_ms=10.0)
    journal.close()
    snap = reg.snapshot()

    def family(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in snap[name]["samples"] if s["value"]}

    faults = family("serve_faults_total")
    assert faults[(("kind", "transient"),)] == 1
    assert faults[(("kind", "poison"),)] == 2     # batch + isolated lane
    assert family("serve_retries_total")[()] == 1
    assert snap["serve_retry_backoff_ms"]["samples"]

    # Replay counters on a restart against the same WAL.
    reg.reset()
    journal2 = Journal(path)
    list(serve_forever(tiny_pipe, [_req("a")], journal=journal2,
                       runner_factory=_fake_factory(), timer=VirtualTimer(),
                       max_batch=2, max_wait_ms=10.0))
    journal2.close()
    snap = reg.snapshot()                         # re-read post-reset
    rep = family("serve_replay_total")
    assert rep[(("kind", "deduped"),)] == 1       # trace copy of 'a'


# ---------------------------------------------------------------------------
# Review regressions: the four confirmed findings from the PR 4 review.
# Each test pins the *fixed* behavior; the failure mode it guards against
# is named in the docstring.
# ---------------------------------------------------------------------------


def test_classify_invalid_argument_is_poison_not_fatal():
    """INVALID_ARGUMENT must stay on the isolation path: the XLA runtime
    raises it for per-input problems too, and classifying it fatal would
    let one poisoned request drain the whole server (review finding 4)."""
    assert classify(RuntimeError(
        "INVALID_ARGUMENT: Executable expected parameter 0 of size 512 "
        "but got 256")) == "poison"
    assert classify(ValueError("invalid_argument: bad operand")) == "poison"


class _InvalidArgRunner(FakeRunner):
    """Raises an XLA-style INVALID_ARGUMENT runtime error for poisoned
    lanes instead of FakeRunner's generic 'poisoned lane' message."""

    def __call__(self, entries, guidance):
        if self.poison & {e.request_id for e in entries}:
            raise RuntimeError(
                "INVALID_ARGUMENT: Executable expected parameter 0 of "
                "size 512 but got 256")
        return super().__call__(entries, guidance)


def test_invalid_argument_error_isolates_instead_of_draining(tiny_pipe):
    """End-to-end blast-radius check for the same finding: one request
    whose execution raises INVALID_ARGUMENT fails alone; every other
    request is still served and the loop does not drain."""
    reqs = [_req(f"r{i}") for i in range(4)]
    recs = _serve(tiny_pipe, reqs, runner_cls=_InvalidArgRunner,
                  poison={"r2"}, max_batch=4, max_wait_ms=10.0)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["r0", "r1", "r3"]
    (err,) = by["error"]
    assert err["request_id"] == "r2"
    assert "fatal" not in by["summary"][0], \
        "a per-request INVALID_ARGUMENT must never drain the server"


class _HungWarmRunner(FakeRunner):
    """warm() blocks in *wall* clock — what a hung in-band XLA compile
    looks like to the engine (no steps, no exception, no return)."""

    def warm(self, entries):
        time.sleep(1.0)


def test_hung_build_with_watchdog_times_out_and_serves_on(tiny_pipe):
    """The watchdog covers the build/warm path, not just execution: a
    compile that hangs on a cache miss becomes timeout records instead of
    wedging the server (review finding 1 — the --watchdog-ms contract)."""
    t0 = time.monotonic()
    recs = _serve(tiny_pipe, [_req("a"), _req("b")],
                  runner_cls=_HungWarmRunner, max_batch=2,
                  max_wait_ms=10.0, watchdog_ms=80.0)
    assert time.monotonic() - t0 < 5.0, "server blocked on a hung compile"
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["timeout"]) == ["a", "b"]
    assert all("build/warm" in r["reason"] for r in by["timeout"])
    assert by["summary"][0]["watchdog_timeouts"] == 1


def test_fatal_drain_covers_not_yet_arrived_trace_requests(tiny_pipe):
    """Exactly-once extends to the trace tail: a fatal fault firing before
    a request's arrival_ms still resolves that request with a terminal
    record instead of silently dropping it (review finding 2)."""
    plan = FaultPlan(by_batch={1: "fatal"})
    reqs = [_req("a"), _req("b"), _req("late", arrival=60_000.0)]
    recs = _serve(tiny_pipe, reqs, chaos=plan, max_batch=2,
                  max_wait_ms=10.0)
    by = _by_status(recs)
    statuses = {r["request_id"]: r["reason"] for r in by["error"]}
    assert set(statuses) == {"a", "b", "late"}
    assert "drained after fatal fault" in statuses["late"]
    seen = sorted(r["request_id"] for r in _terminal(recs))
    assert seen == ["a", "b", "late"], "every trace id exactly once"


def test_shrunken_bucket_never_raises_the_operator_cap():
    """Level-2 degradation shrinks or no-ops — it must never batch wider
    than --max-batch even when --degrade-min-bucket is larger (review
    finding 3)."""
    from p2p_tpu.serve.engine_loop import _shrunken_bucket

    assert _shrunken_bucket(8, 2) == 4
    assert _shrunken_bucket(4, 2) == 2
    assert _shrunken_bucket(2, 1) == 1
    assert _shrunken_bucket(1, 1) == 1
    # Floor above the cap: clamp back to the cap, never grow.
    assert _shrunken_bucket(1, 2) == 1
    assert _shrunken_bucket(2, 4) == 2
    # Floor between one-below and the cap: the floor wins.
    assert _shrunken_bucket(8, 8) == 8


def test_rejected_requests_are_not_counted_as_force_gated(tiny_pipe):
    """Review regression: the degraded-gate counter and the per-record
    ``degraded_gate`` flag must reflect *admissions* — a request rejected
    by backpressure at level >= 1 never ran, so it is neither counted nor
    labeled as force-gated."""
    from p2p_tpu.obs import metrics as metrics_mod

    reg = metrics_mod.registry()
    reg.reset()
    # Distinct compile keys + a long flush wait keep depth high; a tight
    # queue_cap makes the same pressure that trips level 1 also reject.
    reqs = [_req(f"r{i:02d}", arrival=i * 30.0, steps=4 + i)
            for i in range(16)]
    recs = _serve(tiny_pipe, reqs, max_batch=4, max_wait_ms=400.0,
                  queue_cap=4,
                  degrade=DegradeConfig(depth_threshold=2, window_ms=50.0,
                                        min_bucket=1))
    by = _by_status(recs)
    assert by.get("rejected"), "scenario never hit backpressure"
    assert any(r.get("degraded_gate") for r in recs), \
        "scenario never force-gated an admission"
    assert not any(r.get("degraded_gate") for r in by["rejected"]), \
        "a rejected request must never be labeled force-gated"
    snap = reg.snapshot()
    counted = sum(s["value"]
                  for s in snap["serve_degraded_gate_total"]["samples"])
    labeled = sum(1 for r in recs if r.get("degraded_gate"))
    assert counted == labeled, \
        "metric must count only successfully admitted force-gated requests"
