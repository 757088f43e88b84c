"""The bench's own CI: `--preset rehearse` runs every on-accel variant and
secondary block at tiny scale and exits nonzero if any block fails or is
skipped. This pins bench.py against regressions the tiny smoke would never
reach — it already caught a bf16 compile break in the null-text optimizer
before it burned chip time.
"""

import json
import os
import subprocess
import sys

import pytest

from p2p_tpu.utils.cache import default_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPECTED_KEYS = {
    "metric", "value", "unit", "vs_baseline", "variant", "platform",
    "single_group_imgs_per_s",
    "batched_2groups_imgs_per_s", "batched_4groups_imgs_per_s",
    "batched_8groups_imgs_per_s",
    # Phase-gated variant of the headline config (ISSUE 1): rate plus the
    # schema keys that let the trajectory split algorithmic vs kernel wins.
    "batched_4groups_gate05_imgs_per_s", "gate_step", "gate_window_end",
    "phase1_ms_per_step", "phase2_ms_per_step", "phase2_unet_batch",
    # ISSUE 15/16: the nested `gate` record holding the searched per-site
    # reuse-schedule sub-record (GATE_SCHEDULE_KEYS) and the fused-kernel
    # A/B sub-record (GATE_KERNEL_KEYS).
    "gate",
    "dpm20_imgs_per_s", "dpm20_batched_8groups_imgs_per_s",
    "dpm20_batched_4groups_imgs_per_s",
    "reweight_eqsweep_4groups_imgs_per_s",
    "refine_localblend_imgs_per_s",
    "ldm256_8prompt_imgs_per_s",
    # Request-level serving rehearsal (ISSUE 2): the serve block is a nested
    # dict (latency percentiles, occupancy, program-cache hit rate) so the
    # trajectory tracks serving regressions alongside raw throughput.
    "serve",
    # Telemetry overhead (ISSUE 3): instrumented vs plain sampler wall time
    # plus a step-event liveness count, so the BENCH schema records what
    # the observability path costs per round.
    "obs",
    # Resilience drill (ISSUE 4): retry/shed/replay counts and the p95
    # delta the fault-tolerance machinery adds under the standard seeded
    # fault plan, so the trajectory tracks what robustness costs.
    "resilience",
    # Cost observatory (ISSUE 14): the tool-derived PERF.md arithmetic —
    # XLA cost card of the headline U-Net step program + measured
    # step_mfu_pct (a benchwatch headline) per round.
    "cost",
    "nullinv_s_per_image",
}


#: ISSUE 14: the bench `cost` block — frozen literal like the serve
#: sub-records: a key change is a deliberate schema change, updated in the
#: same diff. step_mfu_pct is the benchwatch headline (higher is better).
COST_KEYS = {
    "program", "unet_batch",
    "flops_per_step", "bytes_per_step", "arith_intensity",
    "roofline", "predicted_ms_per_step", "measured_ms_per_step",
    "step_mfu_pct",
    "peak_flops_per_s", "peak_bytes_per_s", "peak_source", "platform",
}


#: ISSUE 15: the `gate` block's `schedule` sub-record — the committed
#: searched reuse-schedule artifact run on the headline operating point.
#: Frozen literal: `speedup` is the benchwatch headline
#: (gate.schedule.speedup, higher is better; the ≥1.5×-over-ungated
#: ISSUE target), `uniform_gate_speedup` is the single-gate ladder rung
#: it is compared against, and `sites_cached` records that the table is
#: genuinely per-site (not a uniform gate in disguise).
GATE_SCHEDULE_KEYS = {
    "artifact", "imgs_per_s", "speedup", "uniform_gate_speedup",
    "cfg_gate_step", "sites_cached", "cached_site_steps_fraction",
    "search_speedup", "ms_per_step",
}


#: ISSUE 16: the `gate` block's `kernel` sub-record — the fused
#: in-kernel-edit attention A/B on the headline operating point. Frozen
#: literal: `speedup` (fused over materialized, higher is better) is the
#: benchwatch headline gate.kernel.speedup; the flash floor is the
#: no-controller ceiling the fused path closes toward; per-variant MFU
#: comes from each variant's own XLA cost card; `interpret` marks CPU
#: rehearsal rounds (pallas interpreter — schema/parity evidence, not
#: speed) so the trajectory never reads a rehearsal ms/step as a chip
#: number.
GATE_KERNEL_KEYS = {
    "fused_imgs_per_s", "fused_ms_per_step",
    "materialized_ms_per_step", "flash_ms_per_step",
    "speedup", "fused_sites", "interpret",
    "fused_mfu_pct", "materialized_mfu_pct", "flash_mfu_pct",
}


#: ISSUE 6: the serve block's `phases` sub-record — the phase-
#: disaggregated two-pool A/B on a gate-mix trace. Frozen literal: a key
#: change here is a deliberate schema change, updated in the same diff.
SERVE_PHASES_KEYS = {
    "n_requests", "handoffs", "handoffs_per_s",
    "phase1_batches", "phase2_batches",
    "phase1_mean_occupancy", "phase2_mean_occupancy",
    "phase2_pack_p50", "phase2_max_batch",
    "single_pool_makespan_ms", "two_pool_makespan_ms", "throughput_ratio",
    "single_pool_p95_ms", "two_pool_p95_ms",
}


#: ISSUE 10: the serve block's `mesh` sub-record — the engine sharded over
#: a dp device mesh at 10x loadgen traffic. Frozen literal so the schema
#: cannot drift before a four-chip run measures the scaling claim: the
#: devices axis, the per-device img/s, the dp=1 vs dp=N scaling ratio and
#: the phase-2 pack width are exactly what the on-chip near-linear-scaling
#: number is recorded from.
SERVE_MESH_KEYS = {
    "devices", "n_requests",
    "dp1_makespan_ms", "mesh_makespan_ms",
    "scaling_ratio", "imgs_per_s_per_device",
    "phase2_pack_p50", "phase2_max_batch", "handoffs",
}


#: ISSUE 12: the serve block's `slo` sub-record — the SLO-tiered 2×
#: overload drill on the deterministic virtual clock. Frozen literal:
#: premium_p99_ratio is a benchwatch headline key (lower is better,
#: bound 1.2× by the quality gate's `slo` check), and the shed split
#: records that best-effort absorbed the overload.
SERVE_SLO_KEYS = {
    "n_requests", "overload_factor",
    "premium_p99_ms", "premium_uncontended_p99_ms", "premium_p99_ratio",
    "best_effort_shed", "paid_shed",
    "preemptions", "preempt_resumes", "quota_rejects",
}


#: ISSUE 13: the serve block's `cache` sub-record — the seeded --zipf 1.1
#: cached-vs-uncached parity drill. Frozen literal: amplification is a
#: benchwatch headline key (img/s served cached over uncached at equal
#: device-seconds of demand, higher is better), and the per-layer hit
#: counts/rates record that all three cache layers actually worked.
SERVE_CACHE_KEYS = {
    "n_requests", "zipf_s",
    "served_from_cache", "served_from_cache_fraction",
    "l1_hits", "l2_hits", "l3_hits",
    "l1_hit_rate", "l2_hit_rate", "l3_hit_rate",
    "l3_evictions", "collapsed",
    "uncached_makespan_ms", "cached_makespan_ms", "amplification",
}


#: ISSUE 18: the serve block's `profile` sub-record — the rehearsal trace
#: re-served with the production profiler sampling 1-in-4 dispatches.
#: Frozen literal: overhead_pct is a benchwatch headline key (lower is
#: better; scale-dependent, the trend is the signal), and captures /
#: sites_measured / ledger_bytes record that the sampled-capture → ledger
#: fold actually produced a consumable workload profile.
SERVE_PROFILE_KEYS = {
    "captures", "sampled_1_in", "sites_measured",
    "ledger_bytes", "overhead_pct", "drift_events",
}


#: ISSUE 19: the serve block's `elastic` sub-record — the three-leg
#: elastic drill (diurnal autonomy, fixed-topology parity, mid-resize
#: kill). Frozen literal: cutover_pause_p95_ms is a benchwatch headline
#: key (lower is better), and the kill leg's keys record that a crash
#: between the durable resize record and cutover restarts on the WAL
#: target topology with every parked carry resumed, exactly-once.
SERVE_ELASTIC_KEYS = {
    "n_requests", "resizes_up", "resizes_down",
    "prewarm_ms", "cutover_pause_p95_ms",
    "parked", "resumed", "dropped",
    "parity_compared", "parity_max_abs", "kill",
}

SERVE_ELASTIC_KILL_KEYS = {
    "killed", "restart_dp", "bitwise_compared",
    "resumed_handoffs", "replay_skipped_corrupt",
}


def test_rehearsal_schema_unchanged_by_static_analysis_pr():
    """ISSUE 5 was a static-analysis PR, ISSUE 6 a serve-architecture PR,
    ISSUE 10 a mesh-serving PR, ISSUE 12 an SLO-scheduling PR and
    ISSUE 13 a semantic-caching PR: the top-level rehearsal schema stays
    exactly the PR-4 set (ISSUE 6 grows the serve block's NESTED `phases`
    sub-record — SERVE_PHASES_KEYS — ISSUE 10 its NESTED `mesh`
    sub-record — SERVE_MESH_KEYS — ISSUE 12 its NESTED `slo` sub-record
    — SERVE_SLO_KEYS — ISSUE 13 its NESTED `cache` sub-record —
    SERVE_CACHE_KEYS — ISSUE 18 its NESTED `profile` sub-record —
    SERVE_PROFILE_KEYS — and ISSUE 19 its NESTED `elastic` sub-record —
    SERVE_ELASTIC_KEYS). A future PR that grows the schema updates the
    frozen copies (and EXPECTED_KEYS, and bench._BLOCK_KEYS) in the same
    diff, deliberately."""
    assert EXPECTED_KEYS == {
        "metric", "value", "unit", "vs_baseline", "variant", "platform",
        "single_group_imgs_per_s",
        "batched_2groups_imgs_per_s", "batched_4groups_imgs_per_s",
        "batched_8groups_imgs_per_s",
        "batched_4groups_gate05_imgs_per_s", "gate_step", "gate_window_end",
        "phase1_ms_per_step", "phase2_ms_per_step", "phase2_unet_batch",
        "gate",  # ISSUE 15: nested searched-schedule sub-record
        "dpm20_imgs_per_s", "dpm20_batched_8groups_imgs_per_s",
        "dpm20_batched_4groups_imgs_per_s",
        "reweight_eqsweep_4groups_imgs_per_s",
        "refine_localblend_imgs_per_s",
        "ldm256_8prompt_imgs_per_s",
        "serve", "obs", "cost", "resilience",
        "nullinv_s_per_image",
    }
    bench = _import_bench()
    assert bench._BLOCK_KEYS == ("gsweep", "gate", "kernel", "dpm",
                                 "dpm_batched", "reweight", "refine_blend",
                                 "ldm256", "serve", "obs", "cost",
                                 "resilience", "nullinv")


def _import_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # One resolver for the whole repo (p2p_tpu.utils.cache): a pre-set
    # JAX_COMPILATION_CACHE_DIR is respected, else the checkout's default
    # the in-process conftest also uses.
    env.setdefault("JAX_COMPILATION_CACHE_DIR", default_cache_dir())
    return env


def _run_bench(*argv, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        env=_cpu_env(), timeout=timeout, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


@pytest.mark.parametrize("argv", [("--preset", "sd14"), ("--preset", "auto"),
                                  ()], ids=["sd14", "auto", "default"])
def test_sd14_measurement_refuses_a_backend_that_is_not_tpu(argv):
    # No fallback: without a chip the sd14 measurement (and `auto`, which
    # means sd14) exits non-zero and prints no metric line.
    proc = _run_bench(*argv)
    assert proc.returncode == 1
    assert "not tpu" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_bench_is_one_process():
    # The measurement runs in the process that was started: a parent that
    # has touched JAX holds the chip, and a child would fail or hang.
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "subprocess" not in src
    assert "--inner" not in src


def test_tiny_preset_prints_one_cpu_labelled_line():
    proc = _run_bench("--preset", "tiny")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc["platform"] == "cpu" and doc["value"] > 0
    # A CPU rate is never printed under the device metric's name or unit.
    assert doc["metric"] == "tiny_cpu_smoke_imgs_per_s"
    assert doc["unit"] == "img/s" and doc["vs_baseline"] == 0.0


def test_secondaries_filter_semantics():
    # The block-narrowing env: honored only for the real sd14 run,
    # never for rehearsal (its CI must keep covering every block) or tiny.
    bench = _import_bench()
    assert bench._secondaries_filter("sd14", None) is None
    assert bench._secondaries_filter("sd14", "") is None
    assert bench._secondaries_filter("rehearse", "ldm256") is None
    assert bench._secondaries_filter("tiny", "ldm256") is None
    got = bench._secondaries_filter("sd14", "ldm256, nullinv")
    assert got == frozenset({"ldm256", "nullinv"})
    with pytest.raises(SystemExit):
        bench._secondaries_filter("sd14", "ldm256,typo")
    # A comma/whitespace-only value is an error, not a skip-everything.
    with pytest.raises(SystemExit):
        bench._secondaries_filter("sd14", " , ")
    # dpm_batched depends on the controller dpm builds: auto-included.
    assert bench._secondaries_filter("sd14", "dpm_batched") == frozenset(
        {"dpm", "dpm_batched"})


def test_prof_experiments_tiny_smoke_lane_validates_qkv():
    """The experiments harness's CPU smoke lane must actually gate the qkv
    A/B: it runs the monkeypatched variant end-to-end at TINY scale and
    hard-asserts bit-exact parity (a dtype regression like the one that
    crashed the 2026-08-01 chip run dies here, not on a scarce window)."""
    env = _cpu_env()
    env["P2P_EXP_PRESET"] = "tiny"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profiling",
                                      "prof_experiments.py"), "--qkv"],
        env=env, cwd=REPO, timeout=600, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert "qkv-fused parity max|Δeps| = 0.000e+00" in proc.stdout
    assert "qkv-fused projections" in proc.stdout


@pytest.mark.slow
def test_bench_rehearsal_green_and_complete():
    proc = _run_bench("--preset", "rehearse", timeout=1500)
    assert proc.returncode == 0, (
        f"rehearsal failed:\n{proc.stderr[-3000:]}")
    last = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    doc = json.loads(last)
    assert doc["metric"] == "bench_rehearsal_imgs_per_s"
    missing = EXPECTED_KEYS - set(doc)
    assert not missing, f"rehearsal line missing keys: {sorted(missing)}"
    assert doc["value"] > 0
    # Rehearsal must never narrow (a stray P2P_BENCH_SECONDARIES is
    # ignored off-sd14): every block above actually ran.
    assert "narrowed" not in doc
    # Serving acceptance (ISSUE 2): the loadgen Poisson trace must keep the
    # batcher at real occupancy with compiles off the request path.
    assert doc["serve"]["mean_batch_occupancy"] >= 2.0
    assert doc["serve"]["program_cache_hit_rate"] >= 0.9
    assert doc["serve"]["p95_ms"] > 0
    # Phase-disaggregated serving acceptance (ISSUE 6): the gate-mix A/B
    # actually crossed the hand-off, phase-2 lanes packed at least as wide
    # as the phase-1 pool ran (continuous batching across requests), and
    # both engines are measured on the same trace. The wall-clock ratio is
    # recorded, not thresholded, at rehearsal scale: a linear-batch-cost
    # CPU host repacks equal compute (~1.0x); the width-restoration win is
    # an accelerator property the recorded keys quantify on a chip run.
    # Searched reuse-schedule acceptance (ISSUE 15): the committed
    # artifact ran on the headline operating point and beat BOTH the
    # ungated baseline (the ≥1.5× target — honestly measurable at CPU
    # rehearsal: the schedule genuinely removes compute) and the single
    # uniform gate (the generalization must pay for itself), with a
    # genuinely per-site table (self sites inherited, not just cross).
    gs = doc["gate"]["schedule"]
    assert set(gs) == GATE_SCHEDULE_KEYS
    assert gs["speedup"] >= 1.5
    assert gs["speedup"] > gs["uniform_gate_speedup"]
    assert gs["sites_cached"]["self"] >= 1
    assert gs["sites_cached"]["cross"] >= 1
    assert 0 < gs["cached_site_steps_fraction"] < 1
    assert gs["cfg_gate_step"] >= 1
    # Fused-kernel A/B acceptance (ISSUE 16): the fused program actually
    # lowered fused sites and all three variants measured. At CPU
    # rehearsal the kernels run through the pallas interpreter
    # (`interpret: true`), so the speedup is recorded — the schema and
    # parity are the rehearsal evidence — but never thresholded here;
    # the ≥1 claim is a chip number, like mesh scaling.
    gk = doc["gate"]["kernel"]
    assert set(gk) == GATE_KERNEL_KEYS
    assert gk["fused_sites"] >= 1
    assert gk["fused_ms_per_step"] > 0
    assert gk["materialized_ms_per_step"] > 0
    assert gk["flash_ms_per_step"] > 0
    assert gk["speedup"] > 0
    assert gk["interpret"] is True  # the rehearsal runs on CPU
    ph = doc["serve"]["phases"]
    assert set(ph) == SERVE_PHASES_KEYS
    assert ph["handoffs"] >= 1
    assert ph["phase2_pack_p50"] >= 2
    assert ph["phase2_mean_occupancy"] >= ph["phase1_mean_occupancy"] - 1e-9
    assert ph["phase2_batches"] <= ph["phase1_batches"]
    assert ph["throughput_ratio"] > 0
    assert ph["single_pool_makespan_ms"] > 0
    assert ph["two_pool_makespan_ms"] > 0
    # Mesh-parallel serving acceptance (ISSUE 10): the mesh leg ran on a
    # real multi-device mesh (the rehearsal inherits the virtual 8-device
    # CPU platform), crossed the hand-off, packed phase-2 lanes into the
    # dp-scaled buckets, and recorded the devices axis + scaling keys a
    # four-chip run will measure. Like the phases A/B, the CPU-rehearsal
    # scaling ratio is recorded, not thresholded (linear batch cost).
    # SLO-tiered overload protection acceptance (ISSUE 12): the 2x
    # overload drill held the premium p99 bound with best-effort
    # absorbing every shed, the quota and preemption machinery actually
    # fired, and the sub-record carries exactly the frozen keys the
    # benchwatch headline (serve.slo.premium_p99_ratio) reads.
    sb = doc["serve"]["slo"]
    assert set(sb) == SERVE_SLO_KEYS
    assert sb["overload_factor"] >= 2.0
    assert sb["premium_p99_ratio"] <= 1.2
    assert sb["best_effort_shed"] >= 1
    assert sb["paid_shed"] == 0
    assert sb["preemptions"] >= 1
    assert sb["quota_rejects"] >= 1
    # Semantic-caching acceptance (ISSUE 13): the zipf parity drill served
    # a real fraction of the trace from cache (the drill itself raises
    # unless every cached serve is bitwise-identical to its uncached
    # twin), every layer hit, the tight L3 budget actually evicted, and
    # the measured img/s amplification — the benchwatch headline — is
    # recorded. Amplification is the one serve win honestly measurable at
    # CPU rehearsal: a cache hit costs no compute on any backend.
    cb = doc["serve"]["cache"]
    assert set(cb) == SERVE_CACHE_KEYS
    assert cb["served_from_cache_fraction"] >= 0.3
    assert cb["l1_hits"] >= 1 and cb["l2_hits"] >= 1 and cb["l3_hits"] >= 1
    assert cb["l3_evictions"] >= 1
    assert cb["amplification"] > 1.0
    assert cb["uncached_makespan_ms"] > cb["cached_makespan_ms"]
    # Production-profiling acceptance (ISSUE 18): the profiler leg
    # actually sampled captures out of the rehearsal trace and folded
    # them into a ledger with measured sites; the capture overhead is
    # recorded honestly (large at CPU-rehearsal dispatch durations —
    # the benchwatch trend on serve.profile.overhead_pct is the signal,
    # never an absolute threshold here).
    pb = doc["serve"]["profile"]
    assert set(pb) == SERVE_PROFILE_KEYS
    assert pb["captures"] >= 1
    assert pb["sampled_1_in"] == 4
    assert pb["sites_measured"] >= 1
    assert pb["ledger_bytes"] > 0
    assert pb["overhead_pct"] >= 0
    assert pb["drift_events"] >= 0
    # Elastic-serving acceptance (ISSUE 19): the diurnal pressure trace
    # really drove the engine up AND down the dp ladder with nothing
    # dropped, every ok output matched the fixed-topology run within the
    # documented vmap tolerance, target programs were prewarmed before
    # cutover (a zero here means a post-cutover in-band compile), and
    # the mid-resize kill restarted on the WAL target topology with the
    # parked carries resumed off their spills — exactly the frozen keys
    # the benchwatch headline (serve.elastic.cutover_pause_p95_ms)
    # reads. The drill raises on any invariant violation, failing the
    # rehearsal outright; these pins freeze the schema.
    eb = doc["serve"]["elastic"]
    assert set(eb) == SERVE_ELASTIC_KEYS
    assert eb["resizes_up"] >= 2
    assert eb["resizes_down"] >= 2
    assert eb["dropped"] == 0
    # The diurnal leg's trace is ungated, so its cutovers park nothing;
    # parked-carry survival is the kill leg's job (resumed_handoffs).
    assert eb["resumed"] == eb["parked"] >= 0
    assert eb["prewarm_ms"] > 0
    assert eb["cutover_pause_p95_ms"] >= 0
    assert eb["parity_compared"] > 0
    assert eb["parity_max_abs"] <= 1
    kb = eb["kill"]
    assert set(kb) == SERVE_ELASTIC_KILL_KEYS
    assert kb["killed"] is True
    assert kb["restart_dp"] == 2
    assert kb["resumed_handoffs"] >= 1
    assert kb["bitwise_compared"] >= 1
    assert kb["replay_skipped_corrupt"] == 0
    mb = doc["serve"]["mesh"]
    assert set(mb) == SERVE_MESH_KEYS
    assert mb["devices"] >= 2            # the virtual mesh really spanned
    assert mb["n_requests"] >= 12
    assert mb["handoffs"] >= 1
    assert mb["phase2_max_batch"] == 4 * mb["devices"]
    assert mb["scaling_ratio"] > 0
    assert mb["imgs_per_s_per_device"] > 0
    assert mb["dp1_makespan_ms"] > 0 and mb["mesh_makespan_ms"] > 0
    # Cost-observatory acceptance (ISSUE 14): the frozen-key cost block
    # carries the headline U-Net step program's XLA cost card and the
    # measured MFU against the calibrated rehearsal peaks — flops pinned
    # exactly deterministic, timing facts present and sane. On CPU the
    # peaks are microbenchmark-calibrated (labeled), never the datasheet.
    cost = doc["cost"]
    assert set(cost) == COST_KEYS
    assert cost["program"] == "unet_step_b4" and cost["unet_batch"] == 4
    assert cost["flops_per_step"] > 0 and cost["bytes_per_step"] > 0
    assert cost["roofline"] in ("compute", "bandwidth")
    assert cost["predicted_ms_per_step"] > 0
    assert cost["measured_ms_per_step"] > 0
    assert cost["step_mfu_pct"] > 0
    assert cost["peak_source"] == "calibrated"
    assert cost["platform"] == "cpu"
    # Resilience acceptance (ISSUE 4): the standard drill must actually
    # drill — faults fired and were retried, ok outputs stayed bitwise-
    # stable vs the fault-free run (run_drill raises otherwise, failing
    # the rehearsal), and the crash-replay found real pending work in the
    # WAL with zero corrupt records on a clean kill.
    res = doc["resilience"]
    assert res["faults_fired"] >= 1
    assert res["retries"] >= 1
    assert res["bitwise_compared"] >= 1
    assert res["replayed_pending"] >= 1
    assert res["replay_skipped_corrupt"] == 0
