"""Engine lifecycle durability (ISSUE 9): journal snapshot/compaction,
graceful drain, warm restart, chaos lifecycle kinds, and the soak drill.

Control-flow properties run against injected runners and a virtual timer
(the test_serve/test_handoff idiom): drains, snapshots and restarts are
fully deterministic under the virtual clock, so exactly-once, fold
equivalence and the strictly-fewer-records compaction win are asserted
exactly. The real-runner rolling-restart leg lives in
tools/quality_gate.py's ``lifecycle`` check; the volume half in
tools/soak.py (rehearsed small here).
"""

import json
import os
import random

import numpy as np
import pytest

from p2p_tpu.serve import (
    DrainController,
    Journal,
    Request,
    SimulatedKill,
    replay,
    serve_forever,
)
from p2p_tpu.serve.chaos import FaultPlan
from p2p_tpu.serve.journal import TERMINAL_STATUSES
from tests.test_serve import FakeRunner, VirtualTimer


def _req(rid, arrival=0.0, steps=4, **kw):
    return Request(request_id=rid, prompt="a cat", target="a dog",
                   steps=steps, arrival_ms=arrival, **kw)


def _by_status(recs):
    out = {}
    for r in recs:
        out.setdefault(r["status"], []).append(r)
    return out


def _serve(tiny_pipe, reqs, timer=None, log=None, **kw):
    timer = timer or VirtualTimer()

    def factory(key, bucket):
        return FakeRunner(key, bucket, timer, log=log)

    return timer, serve_forever(tiny_pipe, reqs, runner_factory=factory,
                                timer=timer, **kw)


def _drain_after(gen, ctl, n_ok, reason="test"):
    """Consume the record stream, requesting a drain after ``n_ok``
    non-rejected terminals — the deterministic drill trigger."""
    recs, count = [], 0
    for rec in gen:
        recs.append(rec)
        if rec.get("status") in TERMINAL_STATUSES and \
                rec["status"] != "rejected":
            count += 1
            if count >= n_ok and not ctl.requested:
                ctl.request(reason)
    return recs


# ---------------------------------------------------------------------------
# Journal snapshot + compaction
# ---------------------------------------------------------------------------


def test_compact_snapshot_rotation_and_warm_fold(tmp_path):
    path = str(tmp_path / "t.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a", "prompt": "x"}, 0.0)
        j.admitted({"request_id": "b", "prompt": "y"}, 1.0)
        j.dispatched(["a"], 1, 2.0)
        j.terminal("a", "ok", 3.0)
        info = j.compact(extra={"degrade_level": 2})
        assert info["seq"] == 1 and info["pending"] == 1
        assert info["terminal"] == 1 and info["wal_records_folded"] == 4
        # Rotated: the WAL is a fresh segment, the old one is gone.
        assert os.path.getsize(path) == 0
        assert not os.path.exists(path + ".old")
        assert os.path.exists(path + ".snapshot")
        j.terminal("b", "ok", 4.0)      # post-snapshot traffic = the tail

    st = replay(path)
    assert st.snapshot_loaded and st.snapshot_seq == 1
    assert st.pending_ids == [] and sorted(st.terminal) == ["a", "b"]
    assert st.degrade_level == 2
    # The compaction win: the tail is strictly smaller than the history.
    assert st.wal_records == 1
    assert st.folded_records == 5
    assert st.wal_records < st.folded_records

    # A second compact stacks: seq bumps, history accumulates.
    with Journal(path) as j:
        info2 = j.compact()
        assert info2["seq"] == 2 and info2["folded_records"] == 5


def test_compact_preserves_pending_handoff_and_its_spill(tmp_path):
    path = str(tmp_path / "h.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "g"}, 0.0)
        spill = j.carry_path("g")
        os.makedirs(os.path.dirname(spill))
        with open(spill, "wb") as f:
            f.write(b"npz-bytes")
        j.handoff("g", 1.0, spill, "PyTreeDef(spec)", trace={"epoch": 0})
        j.compact()
    st = replay(path)
    assert st.pending_ids == ["g"]
    ho = st.handoffs["g"]
    assert ho["carry_path"] == spill and ho["spec"] == "PyTreeDef(spec)"
    assert ho["trace"] == {"epoch": 0}
    assert os.path.exists(spill)        # referenced: survives the GC sweep


def test_orphan_spills_swept_during_replay_with_counter(tmp_path):
    """The ISSUE 9 satellite pin: a crash between open(tmp) and os.replace
    leaves ``*.npz.tmp``; a lost terminal discard leaves an unreferenced
    ``*.npz`` — both planted, both swept, both counted; the referenced
    spill survives."""
    path = str(tmp_path / "o.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "g"}, 0.0)
        spill = j.carry_path("g")
        os.makedirs(os.path.dirname(spill))
        for p in (spill, spill + ".tmp",
                  os.path.join(os.path.dirname(spill), "stale.npz")):
            with open(p, "wb") as f:
                f.write(b"x")
        j.handoff("g", 1.0, spill, "spec")
        j.sync()
    st = replay(path)
    assert st.orphans_swept == 2
    assert os.path.exists(spill)
    assert not os.path.exists(spill + ".tmp")
    assert sorted(os.listdir(os.path.dirname(spill))) == [
        os.path.basename(spill)]
    # Idempotent: a second fold has nothing left to sweep.
    assert replay(path).orphans_swept == 0


def test_corrupt_and_halfwritten_snapshots_fall_back_to_full_wal(tmp_path):
    path = str(tmp_path / "c.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a"}, 0.0)
        j.terminal("a", "ok", 1.0)
        j.admitted({"request_id": "b"}, 2.0)
        j.sync()
    good = replay(path)
    for blob in (b"not json{", b'{"version": 99}',
                 json.dumps({"version": 1, "pending": "nope"}).encode()):
        with open(path + ".snapshot", "wb") as f:
            f.write(blob)
        st = replay(path)
        assert st.snapshot_corrupt == 1 and not st.snapshot_loaded
        assert st.pending == good.pending and st.terminal == good.terminal
    os.remove(path + ".snapshot")
    # A torn .tmp (crash mid-write) never shadows the real snapshot and is
    # swept.
    with open(path + ".snapshot.tmp", "wb") as f:
        f.write(b'{"version": 1, "pend')
    st = replay(path)
    assert st.snapshot_corrupt == 0 and not os.path.exists(
        path + ".snapshot.tmp")
    assert st.pending == good.pending


def test_stale_rotated_segment_is_swept_only_under_a_snapshot(tmp_path):
    path = str(tmp_path / "s.wal")
    with Journal(path) as j:
        j.admitted({"request_id": "a"}, 0.0)
        j.compact()
    # Simulate the crash window between rotation and removal.
    with open(path + ".old", "w") as f:
        f.write(json.dumps({"type": "admitted",
                            "request": {"request_id": "a"},
                            "vnow_ms": 0.0}) + "\n")
    st = replay(path)
    assert st.segments_swept == 1 and not os.path.exists(path + ".old")
    assert st.pending_ids == ["a"]
    # Without a snapshot the segment is the only durable copy: folded,
    # never deleted.
    os.remove(path + ".snapshot")
    with open(path + ".old", "w") as f:
        f.write(json.dumps({"type": "admitted",
                            "request": {"request_id": "z"},
                            "vnow_ms": 0.0}) + "\n")
    st2 = replay(path)
    assert st2.segments_swept == 0 and os.path.exists(path + ".old")
    assert "z" in st2.pending_ids


def test_snapshot_overlapping_wal_folds_idempotently(tmp_path):
    """The crash window between snapshot rename and WAL rotation: the
    snapshot and the un-rotated WAL describe the same records; folding
    both must not double anything."""
    path = str(tmp_path / "i.wal")
    j = Journal(path)
    j.admitted({"request_id": "a"}, 0.0)
    j.terminal("a", "ok", 1.0)
    j.admitted({"request_id": "b"}, 2.0)
    killed = []
    with pytest.raises(SimulatedKill):
        j.compact(on_durable=lambda: killed.append(True) or
                  (_ for _ in ()).throw(SimulatedKill("mid")))
    j._f.close()
    assert killed and os.path.exists(path + ".snapshot")
    assert os.path.getsize(path) > 0        # never rotated
    st = replay(path)
    assert st.snapshot_loaded
    assert st.pending_ids == ["b"] and st.terminal == {"a": "ok"}
    assert st.duplicate_terminals == 1      # the overlap, collapsed


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_replay_fuzz_snapshot_tail_equivalence(tmp_path, seed):
    """Property (ISSUE 9 satellite): random record interleavings with
    garbage injection and mid-record truncation never raise, and folding
    snapshot+tail at ANY cut point equals folding the full WAL."""
    rng = random.Random(seed)
    rids = [f"r{i}" for i in range(12)]
    lines = []
    for _ in range(rng.randint(30, 80)):
        roll = rng.random()
        rid = rng.choice(rids)
        if roll < 0.35:
            rec = {"type": "admitted", "request": {"request_id": rid},
                   "vnow_ms": 0.0}
        elif roll < 0.55:
            rec = {"type": "terminal", "id": rid,
                   "status": rng.choice(TERMINAL_STATUSES), "vnow_ms": 1.0}
        elif roll < 0.7:
            rec = {"type": "handoff", "id": rid,
                   "carry_path": f"/tmp/{rid}.npz", "spec": "s",
                   "vnow_ms": 1.0}
        elif roll < 0.8:
            rec = {"type": "dispatched", "ids": [rid], "batch": 1,
                   "vnow_ms": 1.0}
        elif roll < 0.9:
            rec = {"type": "event", "kind": rng.choice(["degrade",
                                                        "restore"]),
                   "level": rng.randint(0, 3)}
        else:
            lines.append(rng.choice([
                "garbage not json", '{"type": "who knows"}', "{'single'}",
                '{"type": "terminal", "id": "", "status": "ok"}']))
            continue
        lines.append(json.dumps(rec))
    # Mid-record truncation of the tail (the torn-write crash signature).
    torn = lines[-1][:max(1, len(lines[-1]) // 2)]

    full_path = str(tmp_path / f"full{seed}.wal")
    with open(full_path, "w") as f:
        f.write("\n".join(lines + [torn]) + "\n")
    full = replay(full_path, sweep=False)

    cut = rng.randint(0, len(lines))
    snap_path = str(tmp_path / f"snap{seed}.wal")
    with open(snap_path, "w") as f:
        f.write("".join(l + "\n" for l in lines[:cut]))
    with Journal(snap_path) as j:
        j.compact()
    with open(snap_path, "a") as f:
        f.write("".join(l + "\n" for l in lines[cut:]) + torn + "\n")
    st = replay(snap_path, sweep=False)

    assert st.pending == full.pending
    assert st.terminal == full.terminal
    live = set(full.pending_ids)
    assert ({r: st.handoffs[r]["carry_path"]
             for r in st.handoffs if r in live}
            == {r: full.handoffs[r]["carry_path"]
                for r in full.handoffs if r in live})
    assert st.degrade_level == full.degrade_level
    assert st.snapshot_loaded and st.wal_records <= full.wal_records


# ---------------------------------------------------------------------------
# Graceful drain
# ---------------------------------------------------------------------------


def test_drain_completes_inflight_rejects_new_and_snapshots(
        tiny_pipe, tmp_path):
    path = str(tmp_path / "d.wal")
    ctl = DrainController()
    journal = Journal(path)
    # a+b dispatch together (one key); c arrives inside the drain window
    # (vnow has advanced past 50 by then); far never arrives.
    reqs = [_req("a"), _req("b"), _req("c", arrival=50.0),
            _req("far", arrival=1e7)]
    _, gen = _serve(tiny_pipe, reqs, journal=journal, lifecycle=ctl,
                    max_batch=2, max_wait_ms=10.0)
    recs = _drain_after(gen, ctl, 2)
    journal.close()
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["a", "b"]
    # Both the arrived-during-drain request AND the never-arrived trace
    # tail resolve to explicit draining rejections — never a silent drop.
    rejected = {r["request_id"]: r for r in by["rejected"]}
    assert set(rejected) == {"c", "far"}
    assert all("draining" in r["reason"] for r in rejected.values())
    summary = by["summary"][0]
    assert summary["drain"]["reason"] == "test"
    assert summary["drain"]["pending"] == 0
    assert summary["snapshots"] == 1
    # Draining rejections are NOT journaled as terminal: a restart can
    # still serve a resubmission of the same ids.
    st = replay(path)
    assert sorted(st.terminal) == ["a", "b"]


def test_drain_flushes_partial_buckets_without_waiting(tiny_pipe):
    """A drained loop must not sit out max_wait/age timers: an admitted
    entry in a partial young bucket flushes immediately and the loop
    exits, instead of waiting out a (here absurd) 1e6 ms age-out."""
    ctl = DrainController()
    # a0+a1 share a key and flush full; b sits in its own partial bucket.
    reqs = [_req("a0"), _req("a1"), _req("b", steps=5)]
    _, gen = _serve(tiny_pipe, reqs, lifecycle=ctl, max_batch=2,
                    max_wait_ms=1e6)
    recs = _drain_after(gen, ctl, 1)
    by = _by_status(recs)
    assert sorted(r["request_id"] for r in by["ok"]) == ["a0", "a1", "b"]
    assert by["summary"][0]["drain"]["pending"] == 0
    assert by["summary"][0]["makespan_ms"] < 1e5


def test_drain_timeout_journaled_leftovers_resume_exactly_once(
        tiny_pipe, tmp_path):
    """Past the wall-clock drain budget the loop snapshots and exits;
    journaled leftovers get NO terminal record and the warm restart
    serves them exactly once."""
    path = str(tmp_path / "t.wal")
    ctl = DrainController()
    journal = Journal(path)
    timer = VirtualTimer()
    # r0+r1 share a key and flush full (their oks trigger the drain);
    # r2/r3 sit in partial buckets behind an absurd max_wait, so the
    # drain's flush_all is what dispatches them — r2's ~1.1s on the
    # injected wall clock blows the 500ms budget before r3's turn.
    reqs = [_req("r0"), _req("r1"), _req("r2", steps=5),
            _req("r3", steps=6)]
    _, gen = _serve(tiny_pipe, reqs, timer=timer, journal=journal,
                    lifecycle=ctl, max_batch=2, max_wait_ms=1e6,
                    drain_timeout_ms=500.0)
    recs = _drain_after(gen, ctl, 2)
    journal.close()
    by = _by_status(recs)
    summary = by["summary"][0]
    assert summary["drain"]["timed_out"] is True
    served = {r["request_id"] for r in by["ok"]}
    leftover = {r.request_id for r in reqs} - served
    assert leftover, "the timeout must have cut some work"
    # No terminal records for the leftovers in this run...
    assert not any(r.get("request_id") in leftover
                   for r in recs if r.get("status") != "summary")
    # ...and the snapshot carries them as pending.
    st = replay(path)
    assert set(st.pending_ids) == leftover
    # Warm restart over the same trace: leftovers exactly once, dedupe
    # for the already-served.
    journal2 = Journal(path)
    _, gen2 = _serve(tiny_pipe, reqs, journal=journal2, max_batch=2,
                     max_wait_ms=10.0)
    recs2 = list(gen2)
    journal2.close()
    by2 = _by_status(recs2)
    assert {r["request_id"] for r in by2["ok"]} == leftover
    # Every trace copy dedupes: the 3 already-terminal ids AND the
    # re-queued pending one (replay already owns it).
    assert by2["summary"][0]["replay"]["deduped"] == len(reqs)
    assert by2["summary"][0]["replay"]["snapshot"]["seq"] == 1


def test_drain_timeout_without_journal_rejects_leftovers(tiny_pipe):
    """No journal = no restart to hand pending work to: the timeout
    resolves leftovers to explicit draining rejections, never a silent
    drop."""
    ctl = DrainController()
    timer = VirtualTimer()
    reqs = [_req("r0"), _req("r1"), _req("r2", steps=5),
            _req("r3", steps=6)]
    _, gen = _serve(tiny_pipe, reqs, timer=timer, lifecycle=ctl,
                    max_batch=2, max_wait_ms=1e6, drain_timeout_ms=500.0)
    recs = _drain_after(gen, ctl, 2)
    by = _by_status(recs)
    statuses = {r.get("request_id"): r["status"] for r in recs
                if r.get("request_id")}
    assert len(statuses) == 4, "every submitted request got its record"
    assert any(s == "rejected" for s in statuses.values())
    for r in by["rejected"]:
        assert "drain timeout" in r["reason"]


def test_drained_run_is_deterministic(tiny_pipe):
    def run():
        ctl = DrainController()
        reqs = [_req(f"r{i}", arrival=i * 20.0) for i in range(6)]
        _, gen = _serve(tiny_pipe, reqs, lifecycle=ctl, max_batch=2,
                        max_wait_ms=15.0)
        return [{k: v for k, v in r.items() if k != "images"}
                for r in _drain_after(gen, ctl, 3)]

    assert run() == run()


def test_degrade_level_restored_from_snapshot(tiny_pipe, tmp_path):
    from p2p_tpu.serve import DegradeConfig

    path = str(tmp_path / "g.wal")
    snap = {"version": 1, "seq": 3, "pending": [], "handoffs": {},
            "terminal": {}, "degrade_level": 1, "folded_records": 10}
    with open(path + ".snapshot", "w") as f:
        json.dump(snap, f)
    open(path, "w").close()
    journal = Journal(path)
    # Level 1 forces gate='auto' on gate-less admissions from the very
    # first request — proof the level survived the restart.
    _, gen = _serve(tiny_pipe, [_req("a")], journal=journal,
                    degrade=DegradeConfig(depth_threshold=16))
    recs = list(gen)
    journal.close()
    (ok,) = [r for r in recs if r["status"] == "ok"]
    assert ok.get("degraded_gate") is True


# ---------------------------------------------------------------------------
# Chaos lifecycle kinds
# ---------------------------------------------------------------------------


def test_chaos_sigterm_kind_triggers_graceful_drain(tiny_pipe):
    plan = FaultPlan(by_batch={1: "sigterm"})
    reqs = [_req("a"), _req("b", arrival=2000.0)]
    _, gen = _serve(tiny_pipe, reqs, chaos=plan, max_batch=2,
                    max_wait_ms=10.0)
    recs = list(gen)
    by = _by_status(recs)
    # Batch 1 (request a) runs normally — the sigterm lands after it.
    assert [r["request_id"] for r in by["ok"]] == ["a"]
    summary = by["summary"][0]
    assert summary["drain"]["reason"] == "chaos:batch:1"
    # b had not arrived when the drain latched: never served, but still
    # explicitly resolved as a draining rejection.
    (rej,) = by["rejected"]
    assert rej["request_id"] == "b" and "draining" in rej["reason"]


def test_chaos_kill_during_drain_then_restart_exactly_once(
        tiny_pipe, tmp_path):
    path = str(tmp_path / "k.wal")
    plan = FaultPlan(by_batch={1: "sigterm", 2: "kill_during_drain"})
    journal = Journal(path)
    reqs = [_req(f"r{i}", steps=4 + i) for i in range(3)]
    _, gen = _serve(tiny_pipe, reqs, journal=journal, chaos=plan,
                    max_batch=2, max_wait_ms=10.0)
    recs = []
    with pytest.raises(SimulatedKill):
        for rec in gen:
            recs.append(rec)
    journal._f.close()     # simulated process death
    served1 = {r["request_id"] for r in recs if r["status"] == "ok"}
    assert served1, "the drain served something before the kill"
    assert not any(r["status"] == "summary" for r in recs)
    journal2 = Journal(path)
    _, gen2 = _serve(tiny_pipe, reqs, journal=journal2, max_batch=2,
                     max_wait_ms=10.0)
    recs2 = list(gen2)
    journal2.close()
    served2 = {r["request_id"] for r in recs2 if r["status"] == "ok"}
    assert served1 | served2 == {r.request_id for r in reqs}
    assert not served1 & served2, "exactly-once across the kill"


def test_chaos_kill_during_snapshot_restart_folds_idempotently(
        tiny_pipe, tmp_path):
    path = str(tmp_path / "ks.wal")
    plan = FaultPlan(by_batch={1: "kill_during_snapshot"})
    journal = Journal(path)
    timer = VirtualTimer()
    reqs = [_req("a"), _req("b", arrival=30.0, steps=5)]
    _, gen = _serve(tiny_pipe, reqs, timer=timer, journal=journal,
                    chaos=plan, snapshot_every_ms=100.0, max_batch=2,
                    max_wait_ms=10.0)
    recs = []
    with pytest.raises(SimulatedKill):
        for rec in gen:
            recs.append(rec)
    journal._f.close()
    # Died with the snapshot durable but the WAL un-rotated: both exist.
    assert os.path.exists(path + ".snapshot")
    assert os.path.getsize(path) > 0
    served1 = {r["request_id"] for r in recs if r["status"] == "ok"}
    journal2 = Journal(path)
    st = journal2.replay_state
    assert st.snapshot_loaded and st.duplicate_terminals >= 0
    assert set(st.terminal) == served1     # the overlap folded, not doubled
    _, gen2 = _serve(tiny_pipe, reqs, journal=journal2, max_batch=2,
                     max_wait_ms=10.0)
    recs2 = list(gen2)
    journal2.close()
    served2 = {r["request_id"] for r in recs2 if r["status"] == "ok"}
    assert served1 | served2 == {"a", "b"} and not served1 & served2


# ---------------------------------------------------------------------------
# Rolling restart (fake runners) + periodic snapshots
# ---------------------------------------------------------------------------


def test_periodic_snapshots_compact_the_wal(tiny_pipe, tmp_path):
    path = str(tmp_path / "p.wal")
    journal = Journal(path)
    reqs = [_req(f"r{i}", arrival=i * 50.0) for i in range(8)]
    _, gen = _serve(tiny_pipe, reqs, journal=journal,
                    snapshot_every_ms=100.0, max_batch=2, max_wait_ms=10.0)
    recs = list(gen)
    journal.close()
    summary = recs[-1]
    assert summary["snapshots"] >= 2
    st = replay(path)
    assert st.snapshot_loaded
    assert st.wal_records < st.folded_records
    assert set(st.terminal) == {r.request_id for r in reqs}


def test_rolling_restart_fake_exactly_once_and_strictly_fewer(
        tiny_pipe, tmp_path):
    path = str(tmp_path / "roll.wal")
    reqs = [_req(f"r{i}", arrival=i * 10.0) for i in range(12)]
    resolved = {}
    tails = []
    cycles = 3
    for cycle in range(cycles):
        ctl = DrainController()
        journal = Journal(path)
        if cycle > 0:
            tails.append((journal.replay_state.wal_records,
                          journal.replay_state.folded_records))
        _, gen = _serve(tiny_pipe, reqs, journal=journal, lifecycle=ctl,
                        max_batch=2, max_wait_ms=10.0)
        recs = (_drain_after(gen, ctl, 4) if cycle < cycles - 1
                else list(gen))
        journal.close()
        for r in recs:
            if r.get("status") in TERMINAL_STATUSES and \
                    r["status"] != "rejected":
                assert r["request_id"] not in resolved, "resolved twice"
                resolved[r["request_id"]] = r["status"]
    assert set(resolved) == {r.request_id for r in reqs}
    assert all(s == "ok" for s in resolved.values())
    # Every restart replayed a strict tail, not the history.
    for tail, folded in tails:
        assert tail < folded


def test_gated_drain_timeout_spilled_handoffs_resume_in_phase2(
        tiny_pipe, tmp_path):
    """A drain timeout that cuts gated work between its phases leaves the
    journaled hand-off (carry already spilled); the warm restart resumes
    it in phase 2 — not even phase-1 compute repeated. The spill is
    template-shaped, so the resume is real."""
    import jax

    from p2p_tpu.serve.handoff import carry_template

    path = str(tmp_path / "gd.wal")
    timer = VirtualTimer()
    templates = {}

    class GatedFake:
        def __init__(self, key, bucket):
            self.key, self.bucket = key, bucket
            self.tag = key[0] if key else None

        def warm(self, entries):
            timer.advance(1.0)

        def __call__(self, entries, guidance):
            if self.tag == "phase1":
                timer.advance(0.2)
                prep = entries[0].prepared
                if prep.phase2_key not in templates:
                    templates[prep.phase2_key] = jax.tree_util.tree_map(
                        np.asarray, carry_template(tiny_pipe, prep))
                return jax.tree_util.tree_map(
                    lambda x: np.broadcast_to(
                        x[None], (self.bucket,) + x.shape).copy(),
                    templates[prep.phase2_key])
            if self.tag == "phase2":
                for e in entries:
                    assert e.carry is not None
                timer.advance(0.1)
            else:
                timer.advance(0.3)
            return np.zeros((self.bucket, 1, 2, 2, 3), np.uint8)

    def factory(key, bucket):
        return GatedFake(key, bucket)

    # Two full phase-1 batches (distinct keys). The chaos sigterm at the
    # first dispatch latches the drain; both phase-1 batches run in the
    # same cycle (spilling all four carries), then the drain dispatches
    # the first phase-2 batch (~100ms on the injected wall clock) and
    # blows the 50ms budget before the second — g2/g3 stay pending AT THE
    # HAND-OFF, exactly what the snapshot records.
    reqs = [_req("g0", gate=0.5), _req("g1", gate=0.5),
            _req("g2", gate=0.5, steps=5), _req("g3", gate=0.5, steps=5)]
    ctl = DrainController()
    journal = Journal(path)
    recs = list(serve_forever(tiny_pipe, list(reqs), journal=journal,
                              lifecycle=ctl, runner_factory=factory,
                              timer=timer, max_batch=2, max_wait_ms=10.0,
                              phase2_max_batch=2, drain_timeout_ms=50.0,
                              chaos=FaultPlan(by_batch={1: "sigterm"})))
    journal.close()
    summary = recs[-1]
    assert summary["drain"]["timed_out"] is True
    assert summary["phases"]["handoffs"] == 4
    served = {r["request_id"] for r in recs if r.get("status") == "ok"}
    assert len(served) == 2
    pending = {"g0", "g1", "g2", "g3"} - served
    st = replay(path)
    assert set(st.pending_ids) == pending
    assert set(st.handoffs) >= pending

    journal2 = Journal(path)
    recs2 = list(serve_forever(tiny_pipe, list(reqs), journal=journal2,
                               runner_factory=factory, timer=timer,
                               max_batch=2, max_wait_ms=10.0,
                               phase2_max_batch=2))
    journal2.close()
    by2 = _by_status(recs2)
    assert sorted(r["request_id"] for r in by2["ok"]) == sorted(pending)
    summary2 = by2["summary"][0]
    assert summary2["phases"]["resumed_handoffs"] == 2
    assert summary2["phases"]["phase1"]["batches"] == 0   # no re-run


# ---------------------------------------------------------------------------
# Soak rehearsal (small) + loadgen streaming integration
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        f"{name}_for_lifecycle", os.path.join(repo, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_soak_small_rehearsal(tiny_pipe, tmp_path):
    soak = _load_tool("soak")
    report = soak.run_soak(
        tiny_pipe, cycles=3, duration_ms=4000.0, rate_per_s=20.0, seed=3,
        steps=4, snapshot_every_ms=1000.0, drain_timeout_ms=60.0,
        workdir=str(tmp_path / "soak"), min_requests=40, min_cycles=3,
        progress=lambda *_: None)
    assert report["ok"]
    assert report["requests_served"] == report["requests_expected"] >= 40
    assert report["snapshots_total"] >= 3
    disk = report["disk_bytes_per_cycle"]
    assert max(disk) <= report["disk_cap_bytes"]
    assert report["threads_first_last"][0] == report[
        "threads_first_last"][1]


def test_rolling_restart_drill_tool_runs_on_fake_config(
        tiny_pipe, tmp_path):
    """The chaos_drill rolling leg end to end with zero-timer real
    runners at minimal scale — the quality gate runs the full N=3 gated
    version; this pins the tool's plumbing in tier-1."""
    drill = _load_tool("chaos_drill")
    trace = [dict(request_id=f"t{i}", prompt="a cat riding a bike",
                  target="a dog riding a bike", mode="replace", steps=2,
                  seed=100 + i, arrival_ms=float(i * 5))
             for i in range(4)]
    res = drill.rolling_restart_drill(
        tiny_pipe, trace, str(tmp_path / "roll.wal"), cycles=2,
        serve_kw={"timer": lambda: 0.0, "max_batch": 2})
    assert res["counts"] == {"ok": 4}
    assert res["completed_drains"] >= 1
    assert res["bitwise_compared"] == 4
    (tail,) = res["restart_tail_records"]
    assert tail < res["full_history_records"]


# ---------------------------------------------------------------------------
# CLI: SIGINT = graceful drain (the raw-traceback regression)
# ---------------------------------------------------------------------------


def test_serve_cli_sigint_drains_without_traceback(tmp_path):
    """ISSUE 9 satellite: Ctrl-C on a journal-less `serve` used to die
    with a raw KeyboardInterrupt traceback, losing the summary. Now the
    first SIGINT runs the drain path: in-flight work completes, the
    summary (with its `drain` block) is emitted, exit code 0."""
    import signal
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace_path = str(tmp_path / "trace.jsonl")
    results = str(tmp_path / "results.jsonl")
    # Arrivals spread 50 virtual ms apart: admission trickles across many
    # scheduler cycles (each real dispatch advances the virtual clock by
    # its measured wall time), so the SIGINT reliably lands with plenty of
    # trace left — the drain latch is a cycle-boundary event.
    with open(trace_path, "w") as f:
        for i in range(96):
            f.write(json.dumps({
                "request_id": f"s{i}", "prompt": "a cat riding a bike",
                "target": "a dog riding a bike", "mode": "replace",
                "steps": 2, "seed": i, "arrival_ms": i * 50.0}) + "\n")
    wal = str(tmp_path / "cli.wal")
    # The child's output goes to files, not pipes: nothing reads a pipe
    # while this test polls, and XLA's CPU loader can write more than a
    # pipe holds (a line per cached executable), which blocks the child.
    err_path = str(tmp_path / "stderr.txt")
    with open(err_path, "w") as err_f, \
            open(str(tmp_path / "stdout.txt"), "w") as out_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "p2p_tpu.cli", "serve", "--quiet",
             "--requests", trace_path, "--results", results,
             "--max-batch", "8", "--max-wait-ms", "5",
             "--journal", wal, "--snapshot-every-ms", "1000",
             "--drain-timeout-ms", "60000"],
            cwd=repo, stdout=out_f, stderr=err_f,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(results) and any(
                    '"status": "ok"' in l for l in open(results)):
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        else:
            pytest.fail("no ok record within the startup budget")
        assert proc.poll() is None, "served everything before the signal"
        proc.send_signal(signal.SIGINT)
        proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    with open(err_path) as f:
        err = f.read()
    assert proc.returncode == 0, err
    assert "Traceback" not in err
    recs = [json.loads(l) for l in open(results)]
    summary = recs[-1]
    assert summary["status"] == "summary"
    assert summary["drain"]["reason"] == "SIGINT"
    oks = [r for r in recs if r["status"] == "ok"]
    assert oks and len(oks) < 96, "the drain cut the trace short"
    # The drain took its final snapshot; a warm fold sees the served ids
    # terminal and a strict WAL tail.
    assert os.path.exists(wal + ".snapshot")
    st = replay(wal)
    assert st.snapshot_loaded
    assert set(st.terminal) >= {r["request_id"] for r in oks}
    assert st.wal_records < st.folded_records


def test_serve_cli_snapshot_flag_needs_journal(tmp_path):
    """--snapshot-every-ms without --journal is a usage error, raised
    before the (expensive) pipeline build — never a silent no-op."""
    from p2p_tpu.cli import main

    req_path = str(tmp_path / "r.jsonl")
    with open(req_path, "w") as f:
        f.write(json.dumps({"request_id": "a", "prompt": "a cat",
                            "steps": 2, "arrival_ms": 0.0}) + "\n")
    with pytest.raises(SystemExit, match="needs --journal"):
        main(["serve", "--quiet", "--requests", req_path,
              "--snapshot-every-ms", "100"])


# ---------------------------------------------------------------------------
# Exhaustive crash model (ISSUE 20): every bounded interleaving, every cut
# ---------------------------------------------------------------------------


def test_walcheck_tier1_every_crash_point_replays_clean():
    """The exhaustive small-scope leg: every order-preserving interleaving
    of K=2 request paths over ALL declared record kinds, a crash injected
    at every record boundary, every torn tail, and every snapshot window,
    each prefix folded through the real ``replay()`` — zero invariant
    violations, full kind AND window coverage. The scenario tests above
    each pick one adversarial schedule; this leg proves there is no other
    schedule (within tier-1 scope) they missed. FULL_SCOPE (K=3) is the
    slow-marked test in tests/test_walcheck.py."""
    from p2p_tpu.analysis import walcheck

    res = walcheck.run_walcheck(scope=walcheck.TIER1_SCOPE)
    assert res["ok"], res["violations"][:3]
    assert res["kinds_missing"] == [] and res["windows_missing"] == []
    assert set(res["windows"]) == set(
        ("record-boundary", "torn-tail", "snapshot-torn-tmp",
         "snapshot-overlap", "snapshot-stale-old"))
    assert res["crash_points"] > 1_000
