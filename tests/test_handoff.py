"""Phase-disaggregated continuous batching (ISSUE 6): the two-pool serve
engine, the carry hand-off, and its crash-replay semantics.

Three layers of proof:

1. **Numerics** — a gated request served through the split pools (phase-1
   program → hand-off → phase-2 program, lanes packed across requests) is
   bitwise-identical to the same spec through direct gated ``text2image``,
   and the composed pool programs are bitwise-identical to the monolithic
   gated sweep.
2. **Scheduling** — under the virtual clock with fake runners, the
   two-pool control flow (hand-off counts, phase-2 packing across phase-1
   batches, per-phase accounting) is deterministic: same trace + seed ⇒
   identical records and summary across runs.
3. **Durability** — a crash landing *between* a request's phases replays
   exactly-once from the journaled hand-off: the restart resumes the
   request in phase 2 off the spilled carry (no phase-1 re-run), and a
   lost/corrupt spill falls back to a full re-run instead of feeding a
   mismatched carry to a compiled program.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from p2p_tpu.serve import Journal, Request, serve_forever
from p2p_tpu.serve.request import prepare


@pytest.fixture(scope="module")
def tiny_pipe():
    from p2p_tpu.analysis.contracts import tiny_pipeline

    return tiny_pipeline()


def _by_status(recs):
    out = {}
    for r in recs:
        out.setdefault(r["status"], []).append(r)
    return out


def _gated_req(rid, arrival=0.0, gate=0.5, steps=4, seed=None, **kw):
    return Request(request_id=rid, prompt="a cat riding a bike",
                   target="a dog riding a bike", mode="replace",
                   steps=steps, gate=gate, arrival_ms=arrival,
                   seed=seed if seed is not None else abs(hash(rid)) % 1000,
                   **kw)


# ---------------------------------------------------------------------------
# Keys and carry plumbing
# ---------------------------------------------------------------------------


def test_phase_keys_derived_only_for_gated_requests(tiny_pipe):
    gated = prepare(_gated_req("g", gate=0.5), tiny_pipe)
    assert gated.gated
    assert gated.phase1_key[0] == "phase1"
    assert gated.phase2_key[0] == "phase2"
    assert gated.phase2_batch_key == gated.phase2_key + (7.5,)
    ungated = prepare(_gated_req("u", gate=None), tiny_pipe)
    assert not ungated.gated
    assert ungated.phase1_key is None and ungated.phase2_key is None


def test_phase2_key_pools_across_edit_structure(tiny_pipe):
    """The packing claim: attention-edit structure is gone past the gate,
    so replace/refine/equalizer variants share ONE phase-2 pool (and
    therefore one compiled program) while their phase-1 keys differ."""
    rep = prepare(_gated_req("a", gate=0.5), tiny_pipe)
    ref = prepare(dataclasses.replace(_gated_req("b", gate=0.5),
                                      mode="refine"), tiny_pipe)
    eq = prepare(dataclasses.replace(_gated_req("c", gate=0.5),
                                     equalizer="bike=2.0"), tiny_pipe)
    assert rep.phase1_key != ref.phase1_key != eq.phase1_key
    assert rep.phase2_key == ref.phase2_key == eq.phase2_key
    # Gate position stays in both pool keys (the cache-poisoning guard the
    # compile-key sweep enforces).
    other = prepare(_gated_req("d", gate=0.75), tiny_pipe)
    assert other.phase1_key != rep.phase1_key
    assert other.phase2_key != rep.phase2_key


def test_carry_spill_roundtrip_and_spec_validation(tiny_pipe, tmp_path):
    import jax

    from p2p_tpu.engine.sampler import carry_spec
    from p2p_tpu.serve.handoff import (carry_template, lane_carries,
                                       load_carry, spill_carry,
                                       stack_carries)

    prep = prepare(_gated_req("g", gate=0.5), tiny_pipe)
    template = carry_template(tiny_pipe, prep)
    g2 = jax.tree_util.tree_map(lambda x: np.stack([np.asarray(x)] * 2),
                                template)
    lanes = lane_carries(g2, 2)
    assert carry_spec(lanes[0]) == carry_spec(template)
    restacked = stack_carries(lanes[:1], 2)   # pads by replicating
    assert carry_spec(restacked) == carry_spec(g2)

    path = str(tmp_path / "c.npz")
    spec = spill_carry(lanes[0], path)
    assert spec == carry_spec(template)
    loaded = load_carry(path, template)
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(lanes[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # A mismatched spill must refuse loudly, not feed a compiled program.
    bad_template = jax.tree_util.tree_map(
        lambda x: np.zeros((3,) + tuple(x.shape), x.dtype), template)
    with pytest.raises(ValueError, match="does not match"):
        load_carry(path, bad_template)
    with pytest.raises(ValueError, match="unreadable|missing"):
        load_carry(str(tmp_path / "nope.npz"), template)


# ---------------------------------------------------------------------------
# Real-pipeline numerics: pools vs direct gated path
# ---------------------------------------------------------------------------


def test_gated_serving_matches_direct_gated_text2image(tiny_pipe):
    """The hand-off parity contract: requests crossing the two-pool
    boundary (packed with OTHER requests in phase 2) reproduce direct
    gated text2image within the repo's multi-lane vmap tolerance (±1
    uint8 step, the tests/test_serve.py precedent — reassociation across
    batch widths). The strict BITWISE leg of this contract rides the
    single-lane path and is gated by tools/quality_gate.py serve_parity's
    gated case."""
    import jax

    from p2p_tpu.cli import controller_from_opts
    from p2p_tpu.engine.sampler import text2image

    steps = 4
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    reqs = [_gated_req(f"g{i}", gate=0.5, steps=steps, seed=100 + i)
            for i in range(3)]
    recs = list(serve_forever(tiny_pipe, reqs, max_batch=4, max_wait_ms=5.0))
    by = _by_status(recs)
    assert len(by["ok"]) == 3
    got = {r["request_id"]: r for r in by["ok"]}
    ctrl = controller_from_opts(prompts, tiny_pipe.tokenizer, steps,
                                mode="replace", cross_steps=0.8,
                                self_steps=0.4)
    for i in range(3):
        want, _, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=steps,
                                rng=jax.random.PRNGKey(100 + i), gate=0.5)
        d = np.abs(got[f"g{i}"]["images"].astype(np.int16)
                   - np.asarray(want).astype(np.int16))
        assert d.max() <= 1, f"lane g{i} diverged from direct gated path"
        rec = got[f"g{i}"]
        assert rec["gate_step"] == 2
        ph = rec["phases"]
        assert ph["phase1"]["occupancy"] == 3
        assert ph["phase2"]["occupancy"] == 3
        assert ph["handoff_wait_ms"] >= 0.0
    summary = by["summary"][0]
    assert summary["phases"]["handoffs"] == 3
    assert summary["phases"]["phase1"]["batches"] == 1
    assert summary["phases"]["phase2"]["batches"] == 1


def test_phase2_pool_packs_lanes_across_edit_modes(tiny_pipe):
    """replace + refine edits (different phase-1 programs) pack into ONE
    phase-2 batch — and each still matches its direct gated path within
    the multi-lane vmap tolerance."""
    import jax

    from p2p_tpu.cli import controller_from_opts
    from p2p_tpu.engine.sampler import text2image

    steps = 4
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    reqs = [_gated_req("rep", gate=0.5, steps=steps, seed=7),
            dataclasses.replace(_gated_req("ref", gate=0.5, steps=steps,
                                           seed=9), mode="refine")]
    recs = list(serve_forever(tiny_pipe, reqs, max_batch=4, max_wait_ms=5.0))
    by = _by_status(recs)
    assert len(by["ok"]) == 2
    got = {r["request_id"]: r for r in by["ok"]}
    # Two phase-1 batches (incompatible controllers), ONE phase-2 batch.
    summary = by["summary"][0]
    assert summary["phases"]["phase1"]["batches"] == 2
    assert summary["phases"]["phase2"]["batches"] == 1
    assert got["rep"]["phases"]["phase2"]["occupancy"] == 2
    for rid, mode, seed in (("rep", "replace", 7), ("ref", "refine", 9)):
        ctrl = controller_from_opts(prompts, tiny_pipe.tokenizer, steps,
                                    mode=mode, cross_steps=0.8,
                                    self_steps=0.4)
        want, _, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=steps,
                                rng=jax.random.PRNGKey(seed), gate=0.5)
        d = np.abs(got[rid]["images"].astype(np.int16)
                   - np.asarray(want).astype(np.int16))
        assert d.max() <= 1, f"{rid} diverged from direct gated path"


def test_single_pool_flag_is_bitwise_identical_for_gated_traffic(tiny_pipe):
    """phase_pools=False (the A/B baseline) serves gated requests through
    the monolithic program — same images, no phases block."""
    reqs = [_gated_req(f"g{i}", gate=0.5, seed=50 + i) for i in range(2)]
    two = _by_status(list(serve_forever(tiny_pipe, list(reqs), max_batch=2,
                                        max_wait_ms=5.0)))
    one = _by_status(list(serve_forever(tiny_pipe, list(reqs), max_batch=2,
                                        max_wait_ms=5.0,
                                        phase_pools=False)))
    assert len(one["ok"]) == len(two["ok"]) == 2
    a = {r["request_id"]: r for r in two["ok"]}
    b = {r["request_id"]: r for r in one["ok"]}
    for rid in a:
        np.testing.assert_array_equal(a[rid]["images"], b[rid]["images"])
    assert "phases" in two["summary"][0]
    assert "phases" not in one["summary"][0]
    assert "phases" not in b[rid]


# ---------------------------------------------------------------------------
# Virtual-clock scheduling with fake runners
# ---------------------------------------------------------------------------


class PhaseFakeRunner:
    """Deterministic pool-aware stand-in: phase-1 returns a fake carry
    (numpy leaves, so the journal spill path works), phase-2 consumes it.
    Monolithic keys behave like test_serve.FakeRunner."""

    def __init__(self, compile_key, bucket, timer, log=None,
                 p1_s=0.2, p2_s=0.1, mono_s=0.3, warm_s=1.0):
        self.key = compile_key
        self.tag = compile_key[0] if compile_key else None
        self.bucket = bucket
        self.timer = timer
        self.log = log
        self.p1_s, self.p2_s, self.mono_s, self.warm_s = (p1_s, p2_s,
                                                          mono_s, warm_s)
        self.last_lane_finite = None

    def warm(self, entries):
        self.timer.advance(self.warm_s)

    def __call__(self, entries, guidance):
        ids = [e.request_id for e in entries]
        if self.log is not None:
            self.log.append((self.tag or "mono", ids))
        if self.tag == "phase1":
            self.timer.advance(self.p1_s)
            return {"lat": np.zeros((self.bucket, 2, 2), np.float32),
                    "seq": np.arange(self.bucket, dtype=np.int32)}
        if self.tag == "phase2":
            for e in entries:
                assert e.carry is not None, "phase-2 lane without a carry"
            self.timer.advance(self.p2_s)
        else:
            self.timer.advance(self.mono_s)
        return np.zeros((self.bucket, 2, 2, 2, 3), np.uint8)


def _fake_two_pool_serve(tiny_pipe, reqs, log=None, timer=None, **kw):
    from tests.test_serve import VirtualTimer

    timer = timer or VirtualTimer()

    def factory(compile_key, bucket):
        return PhaseFakeRunner(compile_key, bucket, timer, log=log)

    return list(serve_forever(tiny_pipe, reqs, runner_factory=factory,
                              timer=timer, **kw))


def _strip_images(recs):
    return [{k: v for k, v in r.items() if k != "images"} for r in recs]


def test_two_pool_deterministic_under_virtual_clock(tiny_pipe):
    """ISSUE 6 acceptance: same trace + seed ⇒ identical records and
    summary across runs (and identical journal, modulo the spill paths —
    pinned separately below)."""
    def run():
        reqs = [_gated_req(f"g{i}", arrival=i * 10.0, gate=0.5, seed=1)
                for i in range(6)]
        reqs += [_gated_req(f"u{i}", arrival=i * 10.0, gate=None, seed=1)
                 for i in range(3)]
        reqs.sort(key=lambda r: r.arrival_ms)
        return _strip_images(_fake_two_pool_serve(
            tiny_pipe, reqs, max_batch=2, max_wait_ms=15.0,
            phase2_max_batch=4))

    a, b = run(), run()
    assert a == b
    summary = a[-1]
    assert summary["phases"]["handoffs"] == 6
    # Phase-2 packed wider than the phase-1 bucket cap: lanes from
    # different phase-1 batches merged.
    assert summary["phases"]["phase2"]["pack_p50"] >= 2
    assert summary["phases"]["phase1"]["batches"] > \
        summary["phases"]["phase2"]["batches"]


def test_two_pool_journal_is_deterministic(tiny_pipe, tmp_path):
    def run(name):
        path = str(tmp_path / f"{name}.wal")
        reqs = [_gated_req(f"g{i}", arrival=i * 5.0, gate=0.5, seed=1)
                for i in range(4)]
        with Journal(path) as j:
            recs = _fake_two_pool_serve(tiny_pipe, reqs, max_batch=2,
                                        max_wait_ms=15.0, journal=j)
        assert recs[-1]["counts"]["ok"] == 4
        lines = [json.loads(l) for l in open(path)]
        for rec in lines:
            rec.pop("carry_path", None)   # tmp-dir dependent
        return lines

    assert run("a") == run("b")
    kinds = [r["type"] for r in run("c")]
    assert kinds.count("handoff") == 4
    # Hand-off records land between the phase-1 and phase-2 dispatches.
    assert kinds.index("handoff") > kinds.index("dispatched")


def test_phase2_cancel_and_deadline_during_handoff(tiny_pipe):
    """A cancel landing between phases cancels; a deadline expiring during
    the hand-off wait expires — phase-1 compute is written off, the lane
    never dispatches in phase 2."""
    from p2p_tpu.serve import Cancel

    # Timeline (virtual): the 3-of-4 phase-1 batch age-flushes at 400ms,
    # builds+runs (fake warm 1000ms + 200ms), hands off ~1600ms; the
    # partial phase-2 batch age-flushes 400ms later. c's 500ms deadline
    # survives the phase-1 dispatch check (400 < 501) and expires while
    # its carry waits in the phase-2 batcher.
    reqs = [_gated_req("a", arrival=0.0, gate=0.5),
            _gated_req("b", arrival=0.0, gate=0.5),
            _gated_req("c", arrival=1.0, gate=0.5, deadline_ms=500.0),
            Cancel("a")]
    log = []
    recs = _fake_two_pool_serve(tiny_pipe, reqs, log=log, max_batch=4,
                                max_wait_ms=400.0, phase2_max_batch=4)
    by = _by_status(recs)
    assert [r["request_id"] for r in by["cancelled"]] == ["a"]
    (exp,) = by["expired"]
    assert exp["request_id"] == "c" and "hand-off" in exp["reason"]
    assert [r["request_id"] for r in by["ok"]] == ["b"]
    # 'a' and 'c' were cut at the phase-2 boundary: phase-1 ran them, the
    # phase-2 dispatch never carried them.
    p2_ids = [ids for tag, ids in log if tag == "phase2"]
    assert p2_ids == [["b"]]


def test_nan_injected_at_phase1_converts_at_completion(tiny_pipe):
    """A chaos 'nan' fault whose by-batch target is a PHASE-1 dispatch
    must still convert its victim lanes to invalid_output — validation is
    a completion-time verdict, so the injection rides the hand-off
    (matching the monolithic engine, where the same plan poisons the one
    batch)."""
    from p2p_tpu.serve.chaos import FaultPlan

    reqs = [_gated_req("a", arrival=0.0, gate=0.5),
            _gated_req("b", arrival=0.0, gate=0.5)]
    plan = FaultPlan(by_batch={1: "nan"})   # batch 1 = the phase-1 batch
    recs = _fake_two_pool_serve(tiny_pipe, list(reqs), max_batch=2,
                                max_wait_ms=10.0, chaos=plan,
                                validate_outputs=True)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["invalid_output"]) == \
        ["a", "b"]
    assert not by.get("ok")
    # Without --validate-outputs the injection is inert, like mono.
    plan.reset()
    recs = _fake_two_pool_serve(tiny_pipe, list(reqs), max_batch=2,
                                max_wait_ms=10.0, chaos=plan)
    assert sorted(r["request_id"]
                  for r in _by_status(recs)["ok"]) == ["a", "b"]


def test_fatal_fault_drains_phase2_pool_too(tiny_pipe):
    """A fatal fault while hand-offs wait in the phase-2 batcher resolves
    them to error records — nothing hangs in the second pool."""
    from p2p_tpu.serve.chaos import FaultPlan

    reqs = [_gated_req("a", arrival=0.0, gate=0.5),
            _gated_req("b", arrival=0.0, gate=0.5),
            _gated_req("u", arrival=1.0, gate=None, steps=5)]
    # Batch 1 = phase-1 of {a, b} (hand-offs created); batch 2 = the
    # phase-2 batch → fatal. The ungated tail request drains as error.
    plan = FaultPlan(by_batch={2: "fatal"})
    recs = _fake_two_pool_serve(tiny_pipe, reqs, max_batch=2,
                                max_wait_ms=10.0, chaos=plan)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["error"]) == ["a", "b", "u"]
    assert by["summary"][0]["counts"]["ok"] == 0


# ---------------------------------------------------------------------------
# Crash between phases: resume in phase 2, exactly once
# ---------------------------------------------------------------------------


def _crash_at_phase2_factory(pipe):
    """Real runners, except phase-2 dispatch dies — the mid-hand-off
    crash (after the handoff WAL lines + carry spills are durable)."""
    from p2p_tpu.serve.programs import default_runner_factory

    real = default_runner_factory(pipe)

    def factory(key, bucket):
        runner = real(key, bucket)
        if key and key[0] == "phase2":
            class _Crash:
                def warm(self, entries):
                    return runner.warm(entries)

                def __call__(self, entries, guidance):
                    raise KeyboardInterrupt("simulated crash mid-hand-off")

            return _Crash()
        return runner

    return factory


def test_crash_between_phases_resumes_in_phase2_exactly_once(
        tiny_pipe, tmp_path):
    wal = str(tmp_path / "crash.wal")
    reqs = [_gated_req(f"g{i}", gate=0.5, seed=100 + i) for i in range(2)]

    j1 = Journal(wal)
    gen = serve_forever(tiny_pipe, list(reqs), journal=j1,
                        runner_factory=_crash_at_phase2_factory(tiny_pipe),
                        max_batch=2, max_wait_ms=5.0)
    with pytest.raises(KeyboardInterrupt):
        list(gen)
    j1._f.close()  # simulated process death: no clean close

    lines = [json.loads(l) for l in open(wal)]
    kinds = [l["type"] for l in lines]
    assert kinds.count("handoff") == 2 and "terminal" not in kinds
    for rec in lines:
        if rec["type"] == "handoff":
            assert os.path.exists(rec["carry_path"])
            assert rec["spec"].startswith("PyTreeDef")

    # Restart against the same WAL + trace: both requests resume in
    # phase 2 (no phase-1 re-run) and resolve ok exactly once, bitwise
    # vs a clean run.
    j2 = Journal(wal)
    recs = list(serve_forever(tiny_pipe, list(reqs), journal=j2,
                              max_batch=2, max_wait_ms=5.0))
    j2.close()
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["g0", "g1"]
    assert all(r["phases"]["phase1"] == {"resumed": True}
               and r["phases"]["resumed"] for r in by["ok"])
    summary = by["summary"][0]
    assert summary["phases"]["resumed_handoffs"] == 2
    assert summary["phases"]["phase1"]["batches"] == 0   # no re-run
    assert summary["phases"]["phase2"]["batches"] == 1
    assert summary["replay"]["deduped"] == 2             # trace copies

    clean = {r["request_id"]: r
             for r in serve_forever(tiny_pipe, list(reqs), max_batch=2,
                                    max_wait_ms=5.0)
             if r.get("status") == "ok"}
    for r in by["ok"]:
        np.testing.assert_array_equal(r["images"],
                                      clean[r["request_id"]]["images"])


def test_lost_carry_spill_falls_back_to_phase1_rerun(tiny_pipe, tmp_path):
    """A handoff record whose spill is gone (or corrupt) must re-run the
    request from phase 1 — at-least-once compute, exactly-once state,
    never a mismatched carry into a compiled program."""
    wal = str(tmp_path / "lost.wal")
    reqs = [_gated_req("g0", gate=0.5, seed=3)]

    j1 = Journal(wal)
    gen = serve_forever(tiny_pipe, list(reqs), journal=j1,
                        runner_factory=_crash_at_phase2_factory(tiny_pipe),
                        max_batch=2, max_wait_ms=5.0)
    with pytest.raises(KeyboardInterrupt):
        list(gen)
    j1._f.close()
    (spill,) = [l["carry_path"] for l in
                (json.loads(x) for x in open(wal))
                if l["type"] == "handoff"]
    with open(spill, "wb") as f:
        f.write(b"not an npz")

    j2 = Journal(wal)
    recs = list(serve_forever(tiny_pipe, list(reqs), journal=j2,
                              max_batch=2, max_wait_ms=5.0))
    j2.close()
    by = _by_status(recs)
    assert [r["request_id"] for r in by["ok"]] == ["g0"]
    summary = by["summary"][0]
    assert summary["phases"]["resumed_handoffs"] == 0
    assert summary["phases"]["phase1"]["batches"] == 1   # full re-run
    clean = [r for r in serve_forever(tiny_pipe, list(reqs), max_batch=2,
                                      max_wait_ms=5.0)
             if r.get("status") == "ok"]
    np.testing.assert_array_equal(by["ok"][0]["images"],
                                  clean[0]["images"])
