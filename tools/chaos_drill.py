"""Deterministic chaos drill for the fault-tolerant serve loop.

Runs a seeded loadgen trace through ``p2p_tpu.serve.serve_forever`` twice —
once fault-free, once under a seeded ``FaultPlan`` — and asserts the two
drill invariants the fault-tolerance layer promises (ISSUE 4):

1. **Exactly one terminal state.** Every admitted request resolves to
   exactly one of ``ok / rejected / expired / timeout / error /
   invalid_output / cancelled / shed`` — under any fault plan, nothing is
   dropped and nothing is answered twice.
2. **Bitwise-stable outputs.** Every ``ok`` record in the faulted run is
   also ``ok`` in the fault-free run and its image is bitwise-identical:
   retries, lane isolation and warm-bucket re-dispatch may change *when* a
   request runs, never *what* it computes.

``--crash-after K`` adds the crash-replay drill: the first run is
abandoned after K terminal records (a simulated process death; the WAL
keeps only what was flushed), then the loop restarts against the same
``--journal`` file and the same trace — the invariant is that the union of
both runs serves every request exactly once, with no completed request
re-running.

``--rolling N`` adds the lifecycle leg (ISSUE 9): N graceful
drain/restart cycles mid-trace — each drain snapshots + compacts the
journal, each restart warm-resumes from snapshot + WAL tail — must yield
exactly-once terminals, ok-outputs bitwise-identical to the uninterrupted
run, snapshot+tail folds byte-equivalent to the never-compacted shadow
WAL, and restarts that replay *strictly fewer* records than the full
history (asserted, not just measured). ``--kill-mid-drain`` arms a chaos
``kill_during_drain`` in the middle cycle.

The whole drill is virtual-clock deterministic on the random-init tiny
pipeline (no checkpoints), so it doubles as the ``fault_drill`` check in
``tools/quality_gate.py``.

    python tools/chaos_drill.py                      # standard drill
    python tools/chaos_drill.py --n 32 --fault-rate 0.4 --seed 7
    python tools/chaos_drill.py --crash-after 8      # + crash-replay drill
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _pin_cpu():
    """Deterministic CPU backend (same scrub as quality_gate: the drill's
    contract is bitwise, so the platform must be pinned). Called from
    ``main()`` only — importers (tools/quality_gate.py, the tests) choose
    their own backend and must not have theirs scrubbed at import time."""
    from p2p_tpu.utils.cache import default_cache_dir

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          default_cache_dir())


class DrillFailure(AssertionError):
    """An invariant the fault-tolerance layer promises did not hold."""


def tiny_pipeline():
    """Random-init TINY pipeline (the conftest fixture's standalone twin):
    drills need determinism, not checkpoints."""
    import jax

    from p2p_tpu.engine.sampler import Pipeline
    from p2p_tpu.models import TINY, init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    return Pipeline(
        config=TINY,
        unet_params=init_unet(jax.random.PRNGKey(0), TINY.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), TINY.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), TINY.vae),
        tokenizer=HashWordTokenizer(model_max_length=TINY.text.max_length),
    )


def standard_trace(n: int = 24, seed: int = 8, steps: int = 4,
                   fault_rate: float = 0.25, cancel_rate: float = 0.1,
                   kinds=("transient", "poison", "nan"),
                   gate_mix=None):
    """(trace, FaultPlan) pair for the standard drill — all seeded, so
    every caller (CLI, quality gate, bench) drills the identical scenario
    for the same arguments. ``gate_mix`` (a ``loadgen.parse_gate_mix``
    spec string) draws per-request phase gates, so the drill exercises the
    two-pool hand-off path; the default keeps the historical all-ungated
    trace byte-identical."""
    import importlib.util

    from p2p_tpu.serve.chaos import FaultPlan

    spec = importlib.util.spec_from_file_location(
        "p2p_loadgen", os.path.join(_REPO, "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)

    trace = loadgen.generate_trace(
        n, mode="poisson", rate_per_s=50.0, seed=seed, steps=steps,
        gate_mix=(loadgen.parse_gate_mix(gate_mix) if gate_mix else None))
    plan = FaultPlan.from_dict(
        loadgen.fault_plan_dict(trace, seed, fault_rate, kinds=kinds))
    if cancel_rate > 0:
        trace = loadgen.with_cancels(trace, seed, cancel_rate)
    return trace, plan


def _terminal_records(records):
    from p2p_tpu.serve.engine_loop import TERMINAL_STATUSES

    return [r for r in records if r.get("status") in TERMINAL_STATUSES]


def check_exactly_once(trace, records, label: str = "drill") -> dict:
    """Invariant 1: every admitted request id → exactly one terminal
    record. Returns {id: record}."""
    ids = [r["request_id"] for r in trace if "request_id" in r]
    seen: dict = {}
    for rec in _terminal_records(records):
        rid = rec["request_id"]
        if rid in seen:
            raise DrillFailure(
                f"{label}: request {rid!r} resolved twice "
                f"({seen[rid]['status']} then {rec['status']})")
        seen[rid] = rec
    missing = [rid for rid in ids if rid not in seen]
    if missing:
        raise DrillFailure(f"{label}: {len(missing)} request(s) never "
                           f"reached a terminal state: {missing[:5]}")
    extra = set(seen) - set(ids)
    if extra:
        raise DrillFailure(f"{label}: terminal records for ids not in the "
                           f"trace: {sorted(extra)[:5]}")
    return seen


def check_bitwise_vs_clean(clean_by_id: dict, faulted_by_id: dict) -> int:
    """Invariant 2: every faulted-run ``ok`` is ``ok`` in the clean run
    with a bitwise-identical image. Returns how many ids were compared."""
    import numpy as np

    compared = 0
    for rid, rec in faulted_by_id.items():
        if rec["status"] != "ok":
            continue
        clean = clean_by_id.get(rid)
        if clean is None or clean["status"] != "ok":
            raise DrillFailure(
                f"request {rid!r} is ok under faults but "
                f"{clean['status'] if clean else 'missing'} fault-free — "
                "faults must only ever degrade, never manufacture results")
        if not np.array_equal(np.asarray(rec["images"]),
                              np.asarray(clean["images"])):
            raise DrillFailure(
                f"request {rid!r}: output under faults differs from the "
                "fault-free run — retries/isolation changed the numerics")
        compared += 1
    return compared


def _prewarm_reps(pipe, trace):
    """One representative request per distinct compile key — the
    bucket-pinning compile-ahead list (see the comment in run_drill)."""
    from p2p_tpu.serve import Request, prepare

    reps, seen = [], set()
    for d in trace:
        if "request_id" not in d:
            continue
        r = Request.from_dict(d)
        key = prepare(r, pipe).compile_key
        if key not in seen:
            seen.add(key)
            reps.append(r)
    return reps


def run_drill(pipe, trace, plan, *, watchdog_ms=None, journal_path=None,
              crash_after=None, serve_kw=None, warmup: bool = False) -> dict:
    """Run the (clean, faulted[, crash-replay]) drill; raise
    :class:`DrillFailure` on any invariant violation; return the
    resilience summary the bench/quality-gate callers record.

    ``warmup=True`` runs the clean trace once unmeasured first, so the
    measured runs both hit warm compile caches and the reported p95 delta
    is retry/backoff cost, not compile noise."""
    from p2p_tpu.serve import serve_forever

    # phase2_max_batch pinned to max_batch: the drill's bitwise invariant
    # compares clean vs faulted runs whose batch *composition* may differ
    # (wall-clock timing feeds the virtual clock). Padding within one
    # bucket is proven bitwise-invariant; different buckets are only
    # vmap-tolerance-equal — so the drill keeps every pool on one bucket.
    kw = dict(max_batch=4, max_wait_ms=20.0, queue_cap=256,
              validate_outputs=True, phase2_max_batch=4)
    kw.update(serve_kw or {})

    # Bucket-pinning compile-ahead (the PR-5-era "host-drift" resilience
    # flake, root-caused): flush boundaries are host-load-dependent, so
    # without prewarm a partial flush early in one run compiles (and
    # rides) a SMALLER bucket than the same requests hit in the other run
    # — and cross-bucket vmap widths only match to ±1, breaking the
    # bitwise invariant under contention. Warming every distinct compile
    # key at the max bucket makes warm-preference pad every dispatch
    # (full, partial, isolation re-run) to that one bucket, so outputs
    # are composition-independent — and it mirrors what the serve CLI
    # does by default (compile-ahead).
    if "prewarm" not in kw:
        kw["prewarm"] = _prewarm_reps(pipe, trace)

    if warmup:
        for _ in serve_forever(pipe, list(trace), **kw):
            pass
    clean = list(serve_forever(pipe, list(trace), **kw))
    clean_by_id = check_exactly_once(trace, clean, "fault-free run")

    plan.reset()
    faulted = list(serve_forever(pipe, list(trace), chaos=plan,
                                 watchdog_ms=watchdog_ms, **kw))
    faulted_by_id = check_exactly_once(trace, faulted, "faulted run")
    compared = check_bitwise_vs_clean(clean_by_id, faulted_by_id)

    def _counts(by_id):
        out: dict = {}
        for rec in by_id.values():
            out[rec["status"]] = out.get(rec["status"], 0) + 1
        return out

    clean_summary = clean[-1]
    faulted_summary = faulted[-1]
    result = {
        "n_requests": len(clean_by_id),
        "faults_planned": len(plan),
        "clean_counts": _counts(clean_by_id),
        "faulted_counts": _counts(faulted_by_id),
        "bitwise_compared": compared,
        "retries": faulted_summary["retries"],
        "faults": faulted_summary["faults"],
        "watchdog_timeouts": faulted_summary["watchdog_timeouts"],
        "shed": faulted_summary["counts"]["shed"],
        "p95_clean_ms": clean_summary["p95_ms"],
        "p95_faulted_ms": faulted_summary["p95_ms"],
        "p95_delta_ms": faulted_summary["p95_ms"] - clean_summary["p95_ms"],
    }
    if "phases" in faulted_summary:
        # Gate-mixed traces drill the two-pool hand-off path: surface how
        # much of the drill actually crossed it (a gated drill with zero
        # hand-offs would be vacuous).
        result["handoffs"] = faulted_summary["phases"]["handoffs"]

    if crash_after is not None:
        if journal_path is None:
            journal_path = os.path.join(
                tempfile.mkdtemp(prefix="p2p-chaos-"), "drill.wal")
        result["crash_replay"] = crash_replay_drill(
            pipe, trace, journal_path, crash_after, serve_kw=kw)
    return result


def crash_replay_drill(pipe, trace, journal_path, crash_after: int,
                       serve_kw=None) -> dict:
    """Simulated process death after ``crash_after`` terminal records,
    then a journaled restart over the same trace. Invariant: both runs
    together serve every request exactly once — nothing lost, nothing
    re-answered."""
    from p2p_tpu.serve import Journal, serve_forever
    from p2p_tpu.serve.engine_loop import TERMINAL_STATUSES

    kw = dict(serve_kw or {})
    if os.path.exists(journal_path):
        os.remove(journal_path)

    first: list = []
    journal = Journal(journal_path)
    gen = serve_forever(pipe, list(trace), journal=journal, **kw)
    for rec in gen:
        first.append(rec)
        if len(_terminal_records(first)) >= crash_after:
            break
    gen.close()
    # Simulated crash: the loop dies here. Close the raw handle (flush,
    # no final sync) — the WAL keeps whatever the crash left behind.
    journal._f.close()

    journal2 = Journal(journal_path)
    replay = journal2.replay_state
    second = list(serve_forever(pipe, list(trace), journal=journal2, **kw))
    journal2.close()

    # Strict exactly-once: a request that reached *any* terminal state
    # before the crash must not reach one again after the restart. The one
    # legitimate overlap is 'rejected' — duplicate-id admission rejections
    # are deliberately never journaled (a terminal WAL line for the
    # duplicate's id would make replay drop the still-live original).
    seen: dict = {}
    run2 = {r["request_id"]: r["status"] for r in _terminal_records(second)}
    for rec in _terminal_records(first):
        rid = rec["request_id"]
        if rid in run2 and "rejected" not in (rec["status"], run2[rid]):
            raise DrillFailure(
                f"crash-replay: request {rid!r} reached a terminal state in "
                f"both runs ({rec['status']!r}, then {run2[rid]!r})")
        seen.setdefault(rid, rec["status"])
    for rid, status in run2.items():
        seen.setdefault(rid, status)
    ids = [r["request_id"] for r in trace if "request_id" in r]
    missing = [rid for rid in ids if rid not in seen]
    if missing:
        raise DrillFailure(f"crash-replay: {len(missing)} request(s) lost "
                           f"across the crash: {missing[:5]}")
    summary2 = second[-1]
    return {
        "crash_after": crash_after,
        "replayed_pending": len(replay.pending),
        "already_terminal": len(replay.terminal),
        "skipped_corrupt": replay.skipped_corrupt,
        "replay": summary2.get("replay"),
        # Requests the crash caught *between* their phases resume in
        # phase 2 off the journaled hand-off spill (0 when the crash
        # landed elsewhere; the deterministic mid-hand-off case is pinned
        # by tests/test_handoff.py).
        "resumed_handoffs": summary2.get("phases", {}).get(
            "resumed_handoffs", 0),
    }


class _ShadowJournal:
    """A Journal that tees every appended WAL line into a side-car shadow
    file compaction never touches — the drill's full-history oracle: after
    any number of snapshot/rotate cycles, ``replay(shadow)`` is what a
    never-compacted journal would fold, so snapshot+tail correctness is
    *asserted* against it, not assumed."""

    def __init__(self, path, shadow_path):
        from p2p_tpu.serve import Journal

        self._shadow = open(shadow_path, "a", encoding="utf-8")
        self.journal = Journal(path)
        real_append = self.journal._append

        def tee(rec):
            real_append(rec)
            self._shadow.write(json.dumps(rec) + "\n")
            self._shadow.flush()

        self.journal._append = tee

    def close(self):
        self.journal.close()
        self._shadow.close()


def rolling_restart_drill(pipe, trace, journal_path, *, cycles=3,
                          kill_mid_drain=False, serve_kw=None) -> dict:
    """The lifecycle leg (ISSUE 9): N graceful drain/restart cycles
    mid-trace must be invisible in the results.

    Each cycle opens the same journal (warm restart: snapshot + WAL tail),
    re-feeds the full trace (already-terminal ids dedupe; drained-pending
    ones resume), requests a drain after its share of new terminal
    records, and exits through the drain protocol (snapshot + compaction).
    ``kill_mid_drain=True`` additionally arms a chaos ``kill_during_drain``
    in the middle cycle — that drain dies half-way (no compaction, no
    summary) and the next cycle must still restart exactly-once.

    Invariants raised as :class:`DrillFailure`:

    1. exactly-once: every request id reaches exactly one non-``rejected``
       terminal across the union of cycles (draining rejections are
       backpressure, deliberately un-journaled, and may repeat);
    2. bitwise: every ``ok`` image equals the uninterrupted run's;
    3. snapshot+tail ≡ full history: at every restart the live journal's
       fold (pending ids+dicts, terminal map, live hand-offs) is
       byte-equivalent (JSON) to folding the never-compacted shadow WAL;
    4. compaction wins: every restart after a completed drain replays
       strictly fewer WAL records than the full history holds.
    """
    from p2p_tpu.serve import replay as replay_fn
    from p2p_tpu.serve import serve_forever
    from p2p_tpu.serve.chaos import FaultPlan, SimulatedKill
    from p2p_tpu.serve.engine_loop import TERMINAL_STATUSES
    from p2p_tpu.serve.lifecycle import DrainController

    kw = dict(max_batch=4, max_wait_ms=20.0, queue_cap=256,
              validate_outputs=True, phase2_max_batch=4)
    kw.update(serve_kw or {})
    if "prewarm" not in kw:
        kw["prewarm"] = _prewarm_reps(pipe, trace)

    for p in (journal_path, journal_path + ".shadow",
              journal_path + ".snapshot"):
        if os.path.exists(p):
            os.remove(p)

    clean = list(serve_forever(pipe, list(trace), **kw))
    clean_by_id = check_exactly_once(trace, clean, "uninterrupted run")

    n_requests = len(clean_by_id)
    # One share per cycle plus one spare: a drain completes its in-flight
    # work past the trigger, so later cycles must still have enough left
    # to drain again (deterministic either way under a fixed timer).
    quota = max(1, n_requests // (cycles + 1))
    shadow = journal_path + ".shadow"
    resolved: dict = {}
    drains = completed_drains = kills = 0
    restart_tails = []
    full_history_records = 0

    def _shadow_records():
        with open(shadow) as f:
            return sum(1 for l in f if l.strip())

    def _fold_key(state):
        """The comparable fold: pending (ids + dicts, in order), terminal
        map, and live hand-offs keyed to their spill (path + spec)."""
        live = set(state.pending_ids)
        return json.dumps({
            "pending": state.pending,
            "terminal": dict(sorted(state.terminal.items())),
            "handoffs": {rid: {"carry_path": rec["carry_path"],
                               "spec": rec["spec"]}
                         for rid, rec in sorted(state.handoffs.items())
                         if rid in live}}, sort_keys=True)

    for cycle in range(cycles):
        ctl = DrainController()
        sj = _ShadowJournal(journal_path, shadow)
        live_state = sj.journal.replay_state
        if cycle > 0:
            full = replay_fn(shadow, sweep=False)
            if _fold_key(live_state) != _fold_key(full):
                raise DrillFailure(
                    f"rolling-restart cycle {cycle}: snapshot+tail fold "
                    f"diverged from the full-history fold")
            restart_tails.append(live_state.wal_records)
            full_history_records = full.wal_records
            if completed_drains and not \
                    live_state.wal_records < full_history_records:
                raise DrillFailure(
                    f"rolling-restart cycle {cycle}: compaction won "
                    f"nothing — tail replayed {live_state.wal_records} "
                    f"records vs {full_history_records} full history")
        chaos = None
        if kill_mid_drain and cycle == cycles // 2:
            # Armed at the cycle's first dispatch; fires after the first
            # drain-mode dispatch — this drain dies half-way.
            chaos = FaultPlan(by_batch={1: "kill_during_drain"})
        last = cycle == cycles - 1
        count = 0
        killed = False
        gen = serve_forever(pipe, list(trace), journal=sj.journal,
                            lifecycle=ctl, chaos=chaos, **kw)
        recs = []
        try:
            for rec in gen:
                recs.append(rec)
                if rec.get("status") in TERMINAL_STATUSES and \
                        rec["status"] != "rejected":
                    count += 1
                    if not last and count >= quota and not ctl.requested:
                        ctl.request(f"rolling cycle {cycle}")
                        drains += 1
        except SimulatedKill:
            killed = True
            kills += 1
            sj.journal._f.close()   # simulated death: no clean close
            sj._shadow.close()
        if not killed:
            if ctl.requested and recs and "drain" not in recs[-1]:
                raise DrillFailure(f"rolling-restart cycle {cycle}: drain "
                                   f"requested but the summary shows none")
            if ctl.requested:
                completed_drains += 1
            sj.close()
        for rec in recs:
            status = rec.get("status")
            if status not in TERMINAL_STATUSES or status == "rejected":
                continue
            rid = rec["request_id"]
            if rid in resolved:
                raise DrillFailure(
                    f"rolling-restart: request {rid!r} resolved twice "
                    f"({resolved[rid]['status']} then {status})")
            resolved[rid] = rec

    ids = [r["request_id"] for r in trace if "request_id" in r]
    missing = [rid for rid in ids if rid not in resolved]
    if missing:
        raise DrillFailure(f"rolling-restart: {len(missing)} request(s) "
                           f"lost across the cycles: {missing[:5]}")
    bitwise = check_bitwise_vs_clean(clean_by_id, resolved)
    counts: dict = {}
    for rec in resolved.values():
        counts[rec["status"]] = counts.get(rec["status"], 0) + 1
    return {"cycles": cycles,
            "n_requests": n_requests,
            "drains": drains,
            "completed_drains": completed_drains,
            "kills": kills,
            "counts": counts,
            "bitwise_compared": bitwise,
            "restart_tail_records": restart_tails,
            "full_history_records": full_history_records}


class _VirtualTimer:
    """Injected wall clock for the deterministic SLO policy drill."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt_s):
        self.t += dt_s


def _p99(vals):
    """Nearest-rank p99 (0 when empty) — matches the engine's summary
    percentile arithmetic."""
    if not vals:
        return 0.0
    v = sorted(vals)
    idx = min(len(v) - 1, max(0, int(round(0.99 * (len(v) - 1)))))
    return v[idx]


def slo_overload_drill(pipe, *, n=192, seed=11, steps=4, overload=2.0,
                       service_ms=80.0, max_batch=4) -> dict:
    """The SLO policy drill (ISSUE 12): a seeded tenant/tier/gate-mixed
    loadgen trace offered at ``overload``× the engine's service capacity,
    served through the full scheduler (weighted-fair admission, tenant
    quotas, tier-pure batches, phase-boundary preemption, per-tier
    degradation) on a *deterministic virtual clock* — every dispatched
    batch costs exactly ``service_ms`` of injected wall time, so the
    whole overload scenario replays byte-identically and the policy
    verdicts below are facts, not flakes.

    Invariants raised as :class:`DrillFailure`:

    1. **Shed order** — every ``shed`` record is a best-effort request:
       the degradation ladder never sheds a paid tier while best-effort
       traffic exists to absorb it.
    2. **Premium p99 bound** — premium p99 under the 2× overload stays
       within 1.2× of the *uncontended* premium p99 (the same premium
       requests at the same arrival stamps with no competing traffic).
    3. **Exactly-once** — every admitted request resolves to exactly one
       terminal record, preemptions and sheds included.

    Returns the drill's ``slo`` record."""
    import importlib.util

    from p2p_tpu.serve import DegradeConfig, SloConfig, serve_forever

    spec = importlib.util.spec_from_file_location(
        "p2p_loadgen", os.path.join(_REPO, "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)

    # Offered load = overload × capacity: the engine serves max_batch
    # lanes per service_ms quantum, loadgen offers rate requests/s.
    rate = overload * max_batch * 1000.0 / service_ms
    trace = loadgen.generate_trace(
        n, mode="poisson", rate_per_s=rate, seed=seed, steps=steps,
        gate_mix=loadgen.parse_gate_mix("0.5:1,off:1"),
        tenant_mix=loadgen.parse_name_mix("acme:2,globex:1,initech:1"),
        tier_mix=loadgen.parse_name_mix("premium:1,best_effort:3"))
    tier_of = {r["request_id"]: r.get("tier", "standard") for r in trace}

    # Tuned so the drill actually exercises every mechanism: the quota
    # binds (three tenants × 10 < the 2× backlog), preemption parks
    # between-phases best-effort work, the ladder reaches the shed rung
    # within a couple of service quanta, and min_bucket=4 keeps the
    # level-2 shrink a no-op — a shrunken cap would force in-band
    # compiles below the prewarmed bucket, charging premium latency for
    # a *compile*, which is the one cost compile-ahead exists to avoid.
    slo = SloConfig(tenant_quota=10, preempt_depth=8)
    degrade = DegradeConfig(depth_threshold=8, window_ms=service_ms,
                            min_bucket=4)

    def run(reqs):
        from p2p_tpu.serve import Request

        timer = _VirtualTimer()

        class Runner:
            def __init__(self, compile_key, bucket):
                self.bucket = bucket

            def warm(self, entries):
                timer.advance(2 * service_ms / 1000.0)

            def __call__(self, entries, guidance):
                import numpy as np

                timer.advance(service_ms / 1000.0)
                g = len(entries[0].request.prompts)
                return np.zeros((self.bucket, g, 2, 2, 3), np.uint8)

        objs = [Request.from_dict(d) for d in reqs]
        return list(serve_forever(
            pipe, objs, runner_factory=Runner, timer=timer,
            max_batch=max_batch, phase2_max_batch=max_batch,
            max_wait_ms=service_ms, queue_cap=4 * n,
            prewarm=_prewarm_reps(pipe, reqs), slo=slo, degrade=degrade))

    recs = run(trace)
    check_exactly_once(trace, recs, "slo overload run")
    summary = recs[-1]

    def _lat(records, tier):
        return [r["total_ms"] for r in records
                if r.get("status") == "ok"
                and tier_of.get(r.get("request_id")) == tier]

    shed_tiers = [tier_of[r["request_id"]] for r in recs
                  if r.get("status") == "shed"]
    paid_shed = sum(1 for t in shed_tiers if t != "best_effort")
    if paid_shed:
        raise DrillFailure(
            f"slo overload: {paid_shed} paid-tier request(s) shed while "
            f"best-effort traffic existed — the ladder must shed "
            f"best-effort first (shed tiers: {sorted(set(shed_tiers))})")

    # Uncontended baseline: the SAME premium requests at the SAME arrival
    # stamps, with no competing traffic (arrival order is preserved, so
    # the trace stays sorted).
    premium = [r for r in trace if r.get("tier") == "premium"]
    unc = run(premium)
    check_exactly_once(premium, unc, "uncontended premium run")
    p99_over = _p99(_lat(recs, "premium"))
    p99_unc = _p99(_lat(unc, "premium"))
    ratio = p99_over / p99_unc if p99_unc > 0 else 0.0
    if p99_unc <= 0:
        raise DrillFailure("slo overload: uncontended premium p99 is 0 — "
                           "the baseline run served nothing measurable")
    if ratio > 1.2:
        raise DrillFailure(
            f"slo overload: premium p99 {p99_over:.1f}ms is {ratio:.2f}x "
            f"its uncontended p99 {p99_unc:.1f}ms (> 1.2x) — the "
            f"scheduler failed to protect the paid tier")
    slo_block = summary.get("slo", {})
    return {
        "n_requests": n,
        "overload_factor": overload,
        "premium_p99_ms": round(p99_over, 2),
        "premium_uncontended_p99_ms": round(p99_unc, 2),
        "premium_p99_ratio": round(ratio, 4),
        "best_effort_shed": len(shed_tiers) - paid_shed,
        "paid_shed": paid_shed,
        "preemptions": slo_block.get("preemptions", 0),
        "preempt_resumes": slo_block.get("preempt_resumes", 0),
        "quota_rejects": slo_block.get("quota_rejects", 0),
    }


def preempt_kill_drill(pipe, journal_path, *, steps=3,
                       serve_kw=None) -> dict:
    """The preemption durability drill (ISSUE 12): a chaos
    ``preempt_then_kill`` forces a gated request's preemption at its
    phase boundary (carry spilled, ``preempted`` WAL record), then the
    process dies before the parked work resumes. The restart must fold
    the preempted record exactly like a crashed hand-off: the victim
    resumes in phase 2 off the spill, every request reaches exactly one
    terminal across the union of both runs, and every ``ok`` output is
    bitwise-identical to the never-preempted run."""
    from p2p_tpu.serve import (FaultPlan, Journal, Request, SimulatedKill,
                               serve_forever)
    from p2p_tpu.serve.chaos import PREEMPT_THEN_KILL

    prompts = ("a cat riding a bike", "a dog riding a bike")

    def req(rid, arrival, gate=None, seed=0):
        return {"request_id": rid, "prompt": prompts[0],
                "target": prompts[1], "mode": "replace", "steps": steps,
                "seed": seed, "arrival_ms": arrival,
                **({"gate": gate} if gate is not None else {})}

    victim = "pk-victim"
    trace = [req(victim, 0.0, gate=0.5, seed=42),
             req("pk-g1", 1.0, gate=0.5, seed=43),
             req("pk-u0", 2.0, seed=7),
             req("pk-g2", 500.0, gate=0.5, seed=44)]
    kw = dict(max_batch=4, max_wait_ms=20.0, queue_cap=64,
              phase2_max_batch=4)
    kw.update(serve_kw or {})
    if "prewarm" not in kw:
        kw["prewarm"] = _prewarm_reps(pipe, trace)

    def to_reqs():
        return [Request.from_dict(d) for d in trace]

    clean = list(serve_forever(pipe, to_reqs(), **kw))
    clean_by_id = check_exactly_once(trace, clean, "never-preempted run")

    if os.path.exists(journal_path):
        os.remove(journal_path)
    plan = FaultPlan(by_request={victim: PREEMPT_THEN_KILL})
    journal = Journal(journal_path)
    first: list = []
    killed = False
    gen = serve_forever(pipe, to_reqs(), journal=journal, chaos=plan, **kw)
    try:
        for rec in first_iter(gen, first):
            pass
    except SimulatedKill:
        killed = True
        journal._f.close()   # simulated death: no clean close
    if not killed:
        raise DrillFailure("preempt_then_kill never fired — the victim's "
                           "phase boundary was never reached")

    journal2 = Journal(journal_path)
    if victim not in journal2.replay_state.handoffs:
        raise DrillFailure("the preempted record did not fold into the "
                           "replay hand-off map — the victim would re-run "
                           "phase 1 instead of resuming off its spill")
    second = list(serve_forever(pipe, to_reqs(), journal=journal2, **kw))
    journal2.close()

    seen: dict = {}
    run2 = {r["request_id"]: r for r in _terminal_records(second)}
    for rec in _terminal_records(first):
        rid = rec["request_id"]
        if rid in run2 and "rejected" not in (rec["status"],
                                              run2[rid]["status"]):
            raise DrillFailure(
                f"preempt_then_kill: request {rid!r} reached a terminal "
                f"state in both runs ({rec['status']!r}, then "
                f"{run2[rid]['status']!r})")
        seen.setdefault(rid, rec)
    for rid, rec in run2.items():
        seen.setdefault(rid, rec)
    ids = [r["request_id"] for r in trace]
    missing = [rid for rid in ids if rid not in seen]
    if missing:
        raise DrillFailure(f"preempt_then_kill: {len(missing)} request(s) "
                           f"lost across the kill: {missing}")
    bitwise = check_bitwise_vs_clean(clean_by_id, seen)
    summary2 = second[-1]
    resumed = summary2.get("phases", {}).get("resumed_handoffs", 0)
    if resumed < 1:
        raise DrillFailure("the restart served the victim without "
                           "resuming off the preemption spill")
    return {
        "n_requests": len(ids),
        "killed": killed,
        "bitwise_compared": bitwise,
        "resumed_handoffs": resumed,
        "replay_skipped_corrupt": journal2.replay_state.skipped_corrupt,
    }


def cache_parity_drill(pipe, *, n=32, seed=13, steps=3, zipf_s=1.1,
                       zipf_universe=16, gate=0.5, rate_per_s=10.0,
                       l3_bytes=None, serve_kw=None) -> dict:
    """The semantic-cache parity drill (ISSUE 13): a seeded ``--zipf``
    repeat-heavy trace served twice — uncached, then through a fresh
    :class:`~p2p_tpu.serve.SemCache` — must produce **bitwise-identical
    ok outputs** with a real fraction of the traffic served from cache.
    The gate's default-on ``cache_parity`` leg and the bench
    ``serve.cache`` sub-record both read the returned facts.

    Every request is gated (``gate=0.5``) so all three layers are live;
    ``rate_per_s`` spaces virtual arrivals so repeats land both while
    their leader is still in flight (single-flight collapse) and after
    it completed (real L3/L2 lookups) — at a dense rate everything
    collapses and the stores are never read; ``l3_bytes`` defaults to
    two entries' worth of images, so the L3
    budget actually evicts under the zipf universe and repeats of evicted
    content fall through to the L2 prefix store — the drill exercises
    hit, miss, eviction AND the L2 fallback on one deterministic trace.
    The headline number is ``amplification``: images/sec cached over
    images/sec uncached at the identical offered trace (equal
    device-seconds of demand) — the traffic the cache serves without
    computing it."""
    import importlib.util

    import numpy as np

    from p2p_tpu.serve import Request, SemCache, serve_forever

    spec = importlib.util.spec_from_file_location(
        "p2p_loadgen", os.path.join(_REPO, "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)

    trace = loadgen.generate_trace(
        n, mode="poisson", rate_per_s=rate_per_s, seed=seed, steps=steps,
        gate=gate, zipf_s=zipf_s, zipf_universe=zipf_universe)
    kw = dict(max_batch=4, max_wait_ms=20.0, queue_cap=256,
              phase2_max_batch=4)
    kw.update(serve_kw or {})
    if "prewarm" not in kw:
        kw["prewarm"] = _prewarm_reps(pipe, trace)

    def run(semcache):
        return list(serve_forever(pipe,
                                  [Request.from_dict(d) for d in trace],
                                  semcache=semcache, **kw))

    run(None)                                   # warm programs unmeasured
    clean = run(None)
    clean_by_id = check_exactly_once(trace, clean, "uncached run")
    if l3_bytes is None:
        # Two entries' worth: the zipf universe then forces L3 evictions
        # and L2 fallbacks on the same trace.
        sample = next(r["images"] for r in clean if r["status"] == "ok")
        l3_bytes = 2 * int(np.asarray(sample).nbytes)
    sc = SemCache(spill_dir=os.path.join(
        tempfile.mkdtemp(prefix="p2p-semcache-"), "spill"),
        l3_bytes=l3_bytes)
    cached = run(sc)
    cached_by_id = check_exactly_once(trace, cached, "cached run")
    bitwise = check_bitwise_vs_clean(clean_by_id, cached_by_id)
    if bitwise != sum(1 for r in clean_by_id.values()
                      if r["status"] == "ok"):
        raise DrillFailure(
            f"cache parity: cached run served {bitwise} ok vs the "
            f"uncached run's — a cached serve dropped or degraded traffic")

    block = cached[-1]["semcache"]
    served = block["served_from_cache"]
    stats = block["layers"]

    def hit_rate(layer):
        s = stats[layer]
        return round(s["hits"] / max(s["hits"] + s["misses"], 1), 4)

    amp = clean[-1]["makespan_ms"] / max(cached[-1]["makespan_ms"], 1e-9)
    return {
        "n_requests": n,
        "zipf_s": zipf_s,
        "served_from_cache": served,
        "served_from_cache_fraction": round(served / n, 4),
        "l1_hits": stats["l1"]["hits"],
        "l2_hits": stats["l2"]["hits"],
        "l3_hits": stats["l3"]["hits"],
        "l1_hit_rate": hit_rate("l1"),
        "l2_hit_rate": hit_rate("l2"),
        "l3_hit_rate": hit_rate("l3"),
        "l3_evictions": stats["l3"]["evictions"],
        "collapsed": block["served"]["collapsed"],
        "uncached_makespan_ms": round(clean[-1]["makespan_ms"], 1),
        "cached_makespan_ms": round(cached[-1]["makespan_ms"], 1),
        "amplification": round(amp, 3),
    }


def cache_insert_kill_drill(pipe, journal_path, *, steps=3) -> dict:
    """The cache durability drill (ISSUE 13): a chaos
    ``kill_after_cache_insert`` dies between the leader's L3 insert (spill
    + journaled ``cache`` record, both durable) and its terminal fsync.
    The restart must reseed the cache off the journal and serve the
    still-pending leader AND its followers from the durable insert —
    exactly-once across the union of both runs, outputs bitwise-identical
    to the uncached run, zero corrupt records."""
    import numpy as np

    from p2p_tpu.serve import (FaultPlan, Journal, Request, SemCache,
                               SimulatedKill, serve_forever)
    from p2p_tpu.serve.chaos import KILL_AFTER_CACHE_INSERT

    prompts = ("a cat riding a bike", "a dog riding a bike")

    def req(rid, arrival, seed=42):
        return {"request_id": rid, "prompt": prompts[0],
                "target": prompts[1], "mode": "replace", "steps": steps,
                "seed": seed, "gate": 0.5, "arrival_ms": arrival}

    leader = "ck-leader"
    trace = [req(leader, 0.0), req("ck-f1", 1.0), req("ck-f2", 2.0),
             req("ck-distinct", 3.0, seed=9)]
    kw = dict(max_batch=4, max_wait_ms=20.0, queue_cap=64,
              phase2_max_batch=4, prewarm=_prewarm_reps(pipe, trace))

    def to_reqs():
        return [Request.from_dict(d) for d in trace]

    clean = list(serve_forever(pipe, to_reqs(), **kw))
    clean_by_id = check_exactly_once(trace, clean, "uncached run")

    workdir = os.path.dirname(journal_path)
    if os.path.exists(journal_path):
        os.remove(journal_path)
    plan = FaultPlan(by_request={leader: KILL_AFTER_CACHE_INSERT})
    journal = Journal(journal_path)
    sc = SemCache(spill_dir=os.path.join(workdir, "semcache"))
    first: list = []
    killed = False
    gen = serve_forever(pipe, to_reqs(), journal=journal, chaos=plan,
                        semcache=sc, **kw)
    try:
        for rec in first_iter(gen, first):
            pass
    except SimulatedKill:
        killed = True
        journal._f.close()   # simulated death: no clean close
    if not killed:
        raise DrillFailure("kill_after_cache_insert never fired — the "
                           "leader's L3 insert was never reached")

    journal2 = Journal(journal_path)
    if not journal2.replay_state.cache_entries:
        raise DrillFailure("the journaled cache record did not fold into "
                           "replay — the restart would recompute what the "
                           "durable insert already holds")
    sc2 = SemCache(spill_dir=os.path.join(workdir, "semcache"))
    second = list(serve_forever(pipe, to_reqs(), journal=journal2,
                                semcache=sc2, **kw))
    journal2.close()

    seen: dict = {}
    run2 = {r["request_id"]: r for r in _terminal_records(second)}
    for rec in _terminal_records(first):
        rid = rec["request_id"]
        if rid in run2 and "rejected" not in (rec["status"],
                                              run2[rid]["status"]):
            raise DrillFailure(
                f"kill_after_cache_insert: request {rid!r} reached a "
                f"terminal state in both runs ({rec['status']!r}, then "
                f"{run2[rid]['status']!r})")
        seen.setdefault(rid, rec)
    for rid, rec in run2.items():
        seen.setdefault(rid, rec)
    ids = [r["request_id"] for r in trace]
    missing = [rid for rid in ids if rid not in seen]
    if missing:
        raise DrillFailure(f"kill_after_cache_insert: {len(missing)} "
                           f"request(s) lost across the kill: {missing}")
    bitwise = check_bitwise_vs_clean(clean_by_id, seen)
    summary2 = second[-1]
    served = summary2.get("semcache", {}).get("served_from_cache", 0)
    if served < 1:
        raise DrillFailure("the restart recomputed everything — the "
                           "durable cache insert served nothing")
    followers_ok = sum(
        1 for rid in ("ck-f1", "ck-f2")
        if seen.get(rid, {}).get("status") == "ok"
        and np.array_equal(np.asarray(seen[rid]["images"]),
                           np.asarray(clean_by_id[rid]["images"])))
    return {
        "n_requests": len(ids),
        "killed": killed,
        "bitwise_compared": bitwise,
        "followers_bitwise": followers_ok,
        "restart_served_from_cache": served,
        "replay_skipped_corrupt": journal2.replay_state.skipped_corrupt,
    }


def _elastic_real_factory(pipe, timer, service_ms):
    """Mesh-aware, virtual-clock real-runner factory for the elastic
    drills: builds the engine's *real* runner for whatever topology the
    (mesh-tagged) compile key names, charging ``service_ms`` of injected
    virtual time per dispatch — so the diurnal pressure swings are
    deterministic AND the outputs are real pipeline numerics the parity
    check can bite on. One default factory (weight replication included)
    is built lazily per distinct dp and shared by every runner at that
    width."""
    from p2p_tpu.serve.meshing import MESH_KEY_TAG, MeshSpec, build_mesh
    from p2p_tpu.serve.programs import default_runner_factory

    inner_by_dp: dict = {}

    def inner_factory(dp):
        if dp not in inner_by_dp:
            mesh = build_mesh(MeshSpec(dp=dp)) if dp else None
            inner_by_dp[dp] = default_runner_factory(pipe, mesh=mesh)
        return inner_by_dp[dp]

    def make(compile_key, bucket):
        dp = 0  # untagged key = the mesh-less engine (the fixed baseline)
        if (compile_key and isinstance(compile_key[-1], tuple)
                and len(compile_key[-1]) == 3
                and compile_key[-1][0] == MESH_KEY_TAG):
            dp = int(compile_key[-1][2])
        inner = inner_factory(dp)(compile_key, bucket)

        class Wrapped:
            def __init__(self):
                self.bucket = bucket

            def warm(self, entries):
                # Warm time is charged to the virtual clock too, so the
                # engine's prewarm_ms bookkeeping measures something
                # deterministic (the real compile happens out-of-band of
                # the virtual service timeline either way).
                timer.advance(2 * service_ms / 1000.0)
                return inner.warm(entries)

            def __call__(self, entries, guidance):
                timer.advance(service_ms / 1000.0)
                return inner(entries, guidance)

        return Wrapped()

    return make


def elastic_resize_drill(pipe, journal_path=None, *, n=192, seed=19,
                         steps=3, service_ms=60.0, max_batch=2) -> dict:
    """The elastic serving drill (ISSUE 19), three legs:

    1. **Diurnal autonomy** — a seeded loadgen ``--diurnal`` trace (peaks
       well above dp=1 capacity, troughs well below) served with
       ``elastic`` on, real runners on a deterministic virtual clock: the
       engine must resize dp up AND down at least twice each, drop
       nothing (zero rejected/shed), and resolve every request
       exactly-once.
    2. **Fixed-topology parity** — the same trace through the mesh-less
       fixed engine: every ``ok`` output must match within the repo's
       documented vmap tolerance (±1 uint8 step, serve/meshing.py) — a
       resize may change *where* a lane runs, never what it computes
       beyond that bound.
    3. **Mid-resize crash** — a gated burst with chaos
       ``kill_during_resize``: the process dies after the ``resize``
       record is durable but before cutover. The restart must come back
       on the WAL-recorded *target* topology, resume every parked carry
       off its spill, and the union of both runs must be exactly-once
       with ok-outputs bitwise-identical to the uninterrupted elastic
       run.

    Returns the drill's ``elastic`` record."""
    import importlib.util

    import jax
    import numpy as np

    from p2p_tpu.serve import (ElasticConfig, FaultPlan, Journal, Request,
                               SimulatedKill, serve_forever)
    from p2p_tpu.serve.chaos import KILL_DURING_RESIZE

    if len(jax.devices()) < 4:
        raise DrillFailure(
            f"elastic_resize_drill needs >= 4 devices for a 1<->2<->4 dp "
            f"swing; this process has {len(jax.devices())} (virtual CPU "
            f"meshes: --xla_force_host_platform_device_count)")

    spec = importlib.util.spec_from_file_location(
        "p2p_loadgen", os.path.join(_REPO, "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)

    # Offered load swings around dp=1 capacity (max_batch lanes per
    # service_ms): peaks at 3.5x justify growing toward dp=4, troughs at
    # 0.05x let the widened mesh drain and go calm so it shrinks back —
    # several full day-cycles per trace, so the >=2-each resize floor is
    # structural, not lucky. The time-averaged offered rate sits between
    # dp=1 and dp=2 capacity: a frozen dp=1 engine lags the whole trace,
    # the elastic one keeps catching up (which is the point).
    capacity = max_batch * 1000.0 / service_ms
    trace = loadgen.generate_trace(
        n, mode="poisson", rate_per_s=capacity, seed=seed,
        steps=steps, diurnal={"period_ms": 1200.0, "low": 0.05,
                              "high": 3.5})
    cfg = ElasticConfig(up_depth=3, up_window_ms=40.0, down_depth=2,
                        down_window_ms=150.0, cooldown_ms=100.0, max_dp=4)
    kw = dict(max_batch=max_batch, max_wait_ms=20.0, queue_cap=4 * n,
              phase2_max_batch=max_batch)

    def to_reqs(t):
        return [Request.from_dict(d) for d in t]

    def run(elastic):
        timer = _VirtualTimer()
        return list(serve_forever(
            pipe, to_reqs(trace), timer=timer,
            runner_factory=_elastic_real_factory(pipe, timer, service_ms),
            prewarm=_prewarm_reps(pipe, trace), elastic=elastic, **kw))

    recs = run(cfg)
    by_id = check_exactly_once(trace, recs, "elastic diurnal run")
    dropped = sum(1 for r in _terminal_records(recs)
                  if r["status"] in ("rejected", "shed"))
    if dropped:
        raise DrillFailure(f"elastic diurnal run dropped {dropped} "
                           f"request(s) — resizing must add capacity, "
                           f"never shed work")
    summary = recs[-1]
    stats = summary.get("elastic", {})
    if stats.get("resizes_up", 0) < 2 or stats.get("resizes_down", 0) < 2:
        raise DrillFailure(
            f"elastic diurnal run resized up {stats.get('resizes_up')}x / "
            f"down {stats.get('resizes_down')}x — the drill needs >= 2 "
            f"each (timeline: {stats.get('timeline')})")
    if stats.get("prewarm_ms", 0) <= 0:
        raise DrillFailure("resizes committed with zero prewarm time — "
                           "cutovers must compile-ahead, never in-band")

    # Leg 2: fixed-topology parity at the documented vmap tolerance.
    fixed = run(None)
    fixed_by_id = check_exactly_once(trace, fixed, "fixed-topology run")
    max_abs = 0
    compared = 0
    for rid, rec in by_id.items():
        if rec["status"] != "ok":
            continue
        ref = fixed_by_id.get(rid)
        if ref is None or ref["status"] != "ok":
            raise DrillFailure(f"request {rid!r} is ok under elastic but "
                               f"not in the fixed-topology run")
        delta = int(np.max(np.abs(
            np.asarray(rec["images"], np.int16)
            - np.asarray(ref["images"], np.int16)))) if np.asarray(
                rec["images"]).size else 0
        max_abs = max(max_abs, delta)
        compared += 1
    if compared == 0:
        raise DrillFailure("elastic parity compared zero ok outputs")
    if max_abs > 1:
        raise DrillFailure(
            f"elastic vs fixed-topology outputs differ by {max_abs} uint8 "
            f"steps (documented vmap tolerance: 1) — a resize changed "
            f"the numerics")

    # Leg 3: kill_during_resize — die between the durable resize record
    # and cutover; restart on the WAL target topology, exactly-once.
    kill = {}
    if journal_path is not None:
        prompts = ("a cat riding a bike", "a dog riding a bike")
        ktrace = [{"request_id": f"ez-{i}", "prompt": prompts[0],
                   "target": prompts[1], "mode": "replace", "steps": steps,
                   "seed": 40 + i, "gate": 0.5, "arrival_ms": float(i)}
                  for i in range(6)]
        # max_dp=2 + a long cooldown pin the whole post-resize tail to
        # dp=2 in BOTH the uninterrupted and the crashed+restarted run,
        # so the union comparison can demand bitwise equality.
        kcfg = ElasticConfig(up_depth=2, up_window_ms=0.0, down_depth=1,
                             down_window_ms=1e6, cooldown_ms=1e6, max_dp=2)

        def krun(elastic, journal=None, chaos=None, sink=None):
            timer = _VirtualTimer()
            gen = serve_forever(
                pipe, to_reqs(ktrace), timer=timer,
                runner_factory=_elastic_real_factory(pipe, timer,
                                                     service_ms),
                prewarm=_prewarm_reps(pipe, ktrace), elastic=elastic,
                journal=journal, chaos=chaos, **kw)
            if sink is None:
                return list(gen)
            for _ in first_iter(gen, sink):
                pass
            return sink

        kclean = krun(kcfg)
        kclean_by_id = check_exactly_once(ktrace, kclean,
                                          "uninterrupted elastic run")
        if os.path.exists(journal_path):
            os.remove(journal_path)
        plan = FaultPlan(by_request={"ez-0": KILL_DURING_RESIZE})
        journal = Journal(journal_path)
        first: list = []
        killed = False
        try:
            krun(kcfg, journal=journal, chaos=plan, sink=first)
        except SimulatedKill:
            killed = True
            journal._f.close()   # simulated death: no clean close
        if not killed:
            raise DrillFailure("kill_during_resize never fired — no "
                               "resize ran after the kill was armed")

        journal2 = Journal(journal_path)
        if journal2.replay_state.mesh_dp != 2:
            raise DrillFailure(
                f"the WAL's resize record did not fold: replay mesh_dp = "
                f"{journal2.replay_state.mesh_dp}, expected the target "
                f"topology 2")
        second = krun(kcfg, journal=journal2)
        journal2.close()
        restart_timeline = second[-1].get("mesh", {}).get("timeline", [])
        if not restart_timeline or restart_timeline[0]["dp"] != 2:
            raise DrillFailure(
                f"the restart did not resume on the WAL target topology "
                f"(timeline: {restart_timeline})")

        seen: dict = {}
        run2 = {r["request_id"]: r for r in _terminal_records(second)}
        for rec in _terminal_records(first):
            rid = rec["request_id"]
            if rid in run2 and "rejected" not in (rec["status"],
                                                  run2[rid]["status"]):
                raise DrillFailure(
                    f"kill_during_resize: request {rid!r} reached a "
                    f"terminal state in both runs ({rec['status']!r}, "
                    f"then {run2[rid]['status']!r})")
            seen.setdefault(rid, rec)
        for rid, rec in run2.items():
            seen.setdefault(rid, rec)
        missing = [r["request_id"] for r in ktrace
                   if r["request_id"] not in seen]
        if missing:
            raise DrillFailure(f"kill_during_resize: {len(missing)} "
                               f"request(s) lost across the kill: "
                               f"{missing}")
        kbitwise = check_bitwise_vs_clean(kclean_by_id, seen)
        resumed = second[-1].get("phases", {}).get("resumed_handoffs", 0)
        if resumed < 1:
            raise DrillFailure("the restart served the parked carries "
                               "without resuming off their spills")
        kill = {
            "killed": killed,
            "restart_dp": restart_timeline[0]["dp"],
            "bitwise_compared": kbitwise,
            "resumed_handoffs": resumed,
            "replay_skipped_corrupt":
                journal2.replay_state.skipped_corrupt,
        }

    return {
        "n_requests": n,
        "resizes_up": stats["resizes_up"],
        "resizes_down": stats["resizes_down"],
        "prewarm_ms": stats["prewarm_ms"],
        "cutover_pause_p95_ms": stats["cutover_pause_p95_ms"],
        "parked": stats["parked"],
        "resumed": stats["resumed"],
        "dropped": dropped,
        "parity_compared": compared,
        "parity_max_abs": max_abs,
        **({"kill": kill} if kill else {}),
    }


def first_iter(gen, sink):
    """Iterate ``gen`` appending into ``sink`` — keeps the try/except at
    the call site tight while the kill can fire mid-iteration."""
    for rec in gen:
        sink.append(rec)
        yield rec


def main(argv=None) -> int:
    _pin_cpu()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--seed", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--fault-rate", type=float, default=0.25)
    ap.add_argument("--cancel-rate", type=float, default=0.1)
    ap.add_argument("--fault-kinds", default="transient,poison,nan",
                    help="comma list from the chaos catalog "
                         "(p2p_tpu.serve.chaos.KINDS); add 'hang' with "
                         "--watchdog-ms and 'fatal' to drill the drain "
                         "path")
    ap.add_argument("--trace", default=None,
                    help="drill an existing loadgen JSONL trace instead of "
                         "generating one")
    ap.add_argument("--plan", default=None,
                    help="fault-plan JSON for --trace (loadgen "
                         "--fault-rate writes it)")
    ap.add_argument("--watchdog-ms", type=float, default=None)
    ap.add_argument("--crash-after", type=int, default=None, metavar="K",
                    help="also run the crash-replay drill: abandon the "
                         "journaled run after K terminal records, restart, "
                         "assert exactly-once across both")
    ap.add_argument("--journal", default=None,
                    help="WAL path for --crash-after/--rolling "
                         "(default: a tempdir)")
    ap.add_argument("--rolling", type=int, default=None, metavar="N",
                    help="also run the rolling-restart lifecycle leg: N "
                         "graceful drain/restart cycles mid-trace (journal "
                         "snapshot+compaction at each drain) must yield "
                         "exactly-once terminals, ok-outputs bitwise-"
                         "identical to the uninterrupted run, and "
                         "snapshot+tail restarts that replay strictly "
                         "fewer WAL records than the full history")
    ap.add_argument("--kill-mid-drain", action="store_true",
                    help="with --rolling: arm a chaos kill_during_drain in "
                         "the middle cycle (that drain dies half-way; the "
                         "restart must still be exactly-once)")
    ap.add_argument("--slo-overload", action="store_true",
                    help="also run the SLO policy drill (ISSUE 12): a "
                         "tenant/tier-mixed trace at 2x overload on a "
                         "deterministic virtual clock must shed best-"
                         "effort only and hold premium p99 within 1.2x "
                         "of its uncontended p99")
    ap.add_argument("--preempt-kill", action="store_true",
                    help="also run the preemption durability drill "
                         "(ISSUE 12): chaos preempt_then_kill parks a "
                         "gated request's carry then dies; the restart "
                         "must resume it off the spill exactly-once with "
                         "bitwise-identical output")
    ap.add_argument("--cache-parity", action="store_true",
                    help="also run the semantic-cache parity drill "
                         "(ISSUE 13): a seeded --zipf repeat-heavy trace "
                         "served cached vs uncached must be bitwise-"
                         "identical with a real served-from-cache "
                         "fraction (L3 evictions + L2 fallback included)")
    ap.add_argument("--cache-kill", action="store_true",
                    help="also run the cache durability drill (ISSUE 13): "
                         "chaos kill_after_cache_insert dies between the "
                         "leader's L3 insert and its terminal fsync; the "
                         "restart must serve leader+followers off the "
                         "journaled insert exactly-once, bitwise")
    ap.add_argument("--elastic", action="store_true",
                    help="also run the elastic resize drill (ISSUE 19): "
                         "a seeded diurnal trace must resize dp up and "
                         "down >= 2x each with zero drops, match the "
                         "fixed-topology run within the documented vmap "
                         "tolerance, and survive a chaos "
                         "kill_during_resize with the restart resuming "
                         "on the WAL-recorded target topology")
    ap.add_argument("--warmup", action="store_true",
                    help="one unmeasured clean pass first, so the p95 "
                         "delta is retry cost, not compile noise")
    args = ap.parse_args(argv)

    if (args.trace is None) != (args.plan is None):
        ap.error("--trace and --plan go together")
    if args.trace:
        from p2p_tpu.serve.chaos import FaultPlan

        with open(args.trace) as f:
            trace = [json.loads(l) for l in f if l.strip()]
        plan = FaultPlan.load(args.plan)
    else:
        from p2p_tpu.serve import chaos

        kinds = tuple(k for k in args.fault_kinds.split(",") if k)
        unknown = [k for k in kinds if k not in chaos.KINDS]
        if unknown:
            # The catalog is the single vocabulary (ISSUE 20 satellite):
            # a typo'd kind would silently plan zero faults of that kind.
            ap.error(f"--fault-kinds {unknown} not in the chaos catalog "
                     f"(known: {', '.join(chaos.KINDS)})")
        trace, plan = standard_trace(args.n, args.seed, args.steps,
                                     args.fault_rate, args.cancel_rate,
                                     kinds)

    print(f"chaos drill: {sum('request_id' in r for r in trace)} requests, "
          f"{len(plan)} planned faults "
          f"({json.dumps(plan.to_dict()['by_request'], sort_keys=True)})",
          file=sys.stderr)
    pipe = tiny_pipeline()
    try:
        result = run_drill(pipe, trace, plan, watchdog_ms=args.watchdog_ms,
                           journal_path=args.journal,
                           crash_after=args.crash_after, warmup=args.warmup)
        if args.rolling:
            jpath = args.journal or os.path.join(
                tempfile.mkdtemp(prefix="p2p-rolling-"), "rolling.wal")
            result["rolling_restart"] = rolling_restart_drill(
                pipe, [r for r in trace if "cancel" not in r], jpath,
                cycles=args.rolling, kill_mid_drain=args.kill_mid_drain)
        if args.slo_overload:
            result["slo"] = slo_overload_drill(pipe)
        if args.preempt_kill:
            jpath = args.journal or os.path.join(
                tempfile.mkdtemp(prefix="p2p-preempt-"), "preempt.wal")
            result["preempt_kill"] = preempt_kill_drill(pipe, jpath)
        if args.cache_parity:
            result["cache"] = cache_parity_drill(pipe)
        if args.cache_kill:
            jpath = args.journal or os.path.join(
                tempfile.mkdtemp(prefix="p2p-cachekill-"), "cache.wal")
            result["cache_kill"] = cache_insert_kill_drill(pipe, jpath)
        if args.elastic:
            jpath = args.journal or os.path.join(
                tempfile.mkdtemp(prefix="p2p-elastic-"), "elastic.wal")
            result["elastic"] = elastic_resize_drill(pipe, jpath)
    except DrillFailure as e:
        print(f"DRILL FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result, indent=2, sort_keys=True))
    print("drill OK: every request reached exactly one terminal state; "
          f"{result['bitwise_compared']} ok outputs bitwise-identical to "
          "the fault-free run", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
