"""Telemetry subsystem (ISSUE 3): registry semantics, histogram quantile
math vs a reference computation, span nesting + ring eviction, serve-loop
metrics against the virtual-clock record stream, and the disabled-mode
jaxpr/output-identity proof for the sampler.

The load-bearing contracts:

- histograms never store samples — quantiles come from fixed buckets, and
  must land within one bucket of the exact (numpy) percentile;
- the serve summary's raw-list p50/p95 and the registry's
  ``serve_request_total_ms`` histogram must reconcile within one bucket
  (the ISSUE 3 acceptance criterion), exercised on the same virtual-clock
  fake-runner loop test_serve pins control flow with;
- with telemetry disabled nothing is traced into the sampler's program
  (same discipline as ``emit_step(enabled=False)``), and enabling it
  changes wall time only — outputs stay bitwise identical.
"""

import io
import json

import numpy as np
import pytest

from p2p_tpu.obs import device as obs_device
from p2p_tpu.obs import metrics as metrics_mod
from p2p_tpu.obs import spans as spans_mod


# ---------------------------------------------------------------------------
# Registry: families, labels, snapshot/reset, exposition
# ---------------------------------------------------------------------------


def test_counter_gauge_label_semantics():
    reg = metrics_mod.Registry()
    c = reg.counter("reqs_total", "requests", labels=("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels(status="err").inc()
    assert c.labels(status="ok").value == 3
    assert c.labels(status="err").value == 1
    with pytest.raises(ValueError, match="labels"):
        c.labels(code="ok")                      # undeclared label name
    with pytest.raises(ValueError):
        c.labels(status="ok").inc(-1)            # counters are monotonic
    g = reg.gauge("depth")
    g.set(4)
    g.add(-1)
    assert g.value == 3


def test_registration_is_get_or_create_and_kind_mismatch_raises():
    reg = metrics_mod.Registry()
    a = reg.counter("x_total", "first", labels=("k",))
    b = reg.counter("x_total", "second declaration ignored", labels=("k",))
    assert a is b                                 # idempotent re-declare
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")                      # kind mismatch
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("other",))  # label mismatch


def test_snapshot_reset_keeps_child_references_live():
    reg = metrics_mod.Registry()
    fam = reg.counter("c_total")
    child = fam.labels()
    child.inc(5)
    assert reg.snapshot()["c_total"]["samples"] == [
        {"labels": {}, "value": 5.0}]
    reg.reset()
    # Zeroed IN PLACE: long-lived references (ProgramCache counters, queue
    # gauges) keep working across serve runs.
    assert child.value == 0.0
    child.inc()
    assert fam.labels().value == 1.0


def test_histogram_quantiles_within_one_bucket_of_numpy():
    buckets = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0)
    reg = metrics_mod.Registry()
    h = reg.histogram("lat_ms", buckets=buckets)
    rng = np.random.RandomState(0)
    vals = rng.lognormal(mean=2.5, sigma=1.0, size=500)
    for v in vals:
        h.observe(float(v))
    assert h.count == 500
    assert h.sum == pytest.approx(vals.sum())
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        exact = float(np.percentile(vals, q * 100))
        # The acceptance grain everywhere: same or adjacent bucket.
        assert abs(h.bucket_index(est) - h.bucket_index(exact)) <= 1, \
            f"q={q}: estimate {est} vs exact {exact}"
    # Degenerate cases stay sane.
    empty = metrics_mod.Histogram(buckets)
    assert empty.quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        metrics_mod.Histogram((5.0, 1.0))         # non-ascending bounds


def test_prometheus_exposition_format():
    reg = metrics_mod.Registry()
    reg.counter("req_total", "requests", labels=("status",)).labels(
        status="ok").inc(2)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(3.0)
    h.observe(99.0)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{status="ok"} 2' in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_count 3" in text
    # Label values are escaped, not trusted.
    reg.counter("esc_total", labels=("p",)).labels(p='a"b\nc').inc()
    assert '\\"' in reg.to_prometheus() and "\\n" in reg.to_prometheus()


def test_jsonl_export_roundtrips():
    reg = metrics_mod.Registry()
    reg.gauge("depth").set(7)
    reg.histogram("h_ms", buckets=(1.0, 2.0)).observe(1.5)
    buf = io.StringIO()
    n = reg.write_jsonl(buf)
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert n == len(lines) == 2
    by_name = {l["metric"]: l for l in lines}
    assert by_name["depth"]["value"] == 7
    assert by_name["h_ms"]["count"] == 1
    assert by_name["h_ms"]["buckets"] == [[1.0, 0], [2.0, 1]]


# ---------------------------------------------------------------------------
# Spans: nesting, ring eviction, export
# ---------------------------------------------------------------------------


def test_span_nesting_parent_depth_duration():
    spans_mod.clear()
    with spans_mod.span("outer", lanes=4):
        with spans_mod.span("inner"):
            pass
    evs = spans_mod.events()
    assert [e["event"] for e in evs] == [
        "span_start", "span_start", "span_end", "span_end"]
    outer_start, inner_start, inner_end, outer_end = evs
    assert outer_start["name"] == "outer" and outer_start["lanes"] == 4
    assert inner_start["parent"] == outer_start["span"]
    assert inner_start["depth"] == 1 and outer_start["depth"] == 0
    assert 0.0 <= inner_end["dur_ms"] <= outer_end["dur_ms"]
    # Durations also land in the registry histogram by span name.
    fam = metrics_mod.registry().get("span_duration_ms")
    assert fam.labels(name="outer").count >= 1


def test_span_ring_buffer_evicts_oldest_and_reports_drops():
    rec = spans_mod.SpanRecorder(capacity=4)
    for i in range(10):
        rec.emit({"event": "span_start", "i": i})
    evs = rec.events()
    assert len(evs) == 4
    assert [e["i"] for e in evs] == [6, 7, 8, 9]   # oldest evicted first
    assert rec.total == 10 and rec.dropped == 6
    buf = io.StringIO()
    # write_jsonl reports the global recorder; meta-line semantics checked
    # on a local buffer by swapping it in.
    old = spans_mod._recorder
    spans_mod._recorder = rec
    try:
        spans_mod.write_jsonl(buf)
    finally:
        spans_mod._recorder = old
    meta = json.loads(buf.getvalue().splitlines()[0])
    assert meta == {"event": "meta", "total": 10, "dropped": 6}


def test_span_disabled_is_pass_through():
    spans_mod.clear()
    spans_mod.set_enabled(False)
    try:
        with spans_mod.span("ghost"):
            pass
        assert spans_mod.events() == []
    finally:
        spans_mod.set_enabled(True)


def test_span_ring_capacity_configurable_and_drop_count_stays_honest():
    """ISSUE 7 satellite: two-pool serving roughly doubles event volume,
    so the ring is sizeable (``serve --events-ring`` /
    ``P2P_OBS_EVENTS_RING``) — and resizing must keep the meta line's
    ``dropped`` count truthful: ``total`` survives a resize, a shrink
    counts its evictions exactly like organic overflow."""
    rec = spans_mod.SpanRecorder(capacity=8)
    for i in range(10):
        rec.emit({"event": "span_start", "i": i})
    assert rec.dropped == 2
    rec.resize(4)                       # shrink: 4 more evicted, counted
    assert rec.capacity == 4
    assert [e["i"] for e in rec.events()] == [6, 7, 8, 9]
    assert rec.total == 10 and rec.dropped == 6
    rec.resize(16)                      # grow: nothing lost, count kept
    assert rec.dropped == 6
    for i in range(10, 14):
        rec.emit({"event": "span_start", "i": i})
    assert len(rec.events()) == 8 and rec.total == 14 and rec.dropped == 6
    with pytest.raises(ValueError, match="capacity"):
        rec.resize(0)
    # The module-level knob targets the process recorder.
    old_cap = spans_mod.capacity()
    try:
        spans_mod.set_capacity(512)
        assert spans_mod.capacity() == 512
    finally:
        spans_mod.set_capacity(old_cap)


def test_span_attach_stamps_context_attributes():
    """ISSUE 7: ``spans.attach`` rides request identity into every span
    opened inside the block (start AND end events), nested attaches merge
    innermost-wins, and explicit span attrs beat attached ones."""
    spans_mod.clear()
    with spans_mod.attach(traces="r1#0", pool="phase1"):
        with spans_mod.span("serve.batch", lanes=2):
            pass
        with spans_mod.attach(pool="phase2"):
            with spans_mod.span("serve.batch", pool="explicit"):
                pass
    with spans_mod.span("serve.batch"):
        pass
    evs = spans_mod.events()
    first_start, first_end = evs[0], evs[1]
    assert first_start["traces"] == "r1#0" and first_start["pool"] == \
        "phase1"
    assert first_end["traces"] == "r1#0" and first_start["lanes"] == 2
    nested_start = evs[2]
    assert nested_start["traces"] == "r1#0"
    assert nested_start["pool"] == "explicit"   # span attrs win
    outside = evs[4]
    assert "traces" not in outside              # attach scope ended


# ---------------------------------------------------------------------------
# Serve loop: registry aggregates reconcile with the record stream
# ---------------------------------------------------------------------------


def _serve_fixture(tiny_pipe, n=24):
    from tests.test_serve import _fake_serve, _req

    # Spread arrivals so queue waits vary; identical specs so one program.
    reqs = [_req(f"r{i:02d}", arrival=i * 20.0) for i in range(n)]
    return _fake_serve(tiny_pipe, reqs, max_batch=4, max_wait_ms=30.0)


def test_serve_metrics_match_record_stream(tiny_pipe):
    reg = metrics_mod.registry()
    reg.reset()
    recs = _serve_fixture(tiny_pipe)
    summary = recs[-1]
    assert summary["status"] == "summary"
    oks = [r for r in recs if r["status"] == "ok"]
    snap = reg.snapshot()

    def sample(name, **labels):
        for s in snap[name]["samples"]:
            if s["labels"] == labels:
                return s
        raise AssertionError(f"{name}{labels} not in snapshot")

    assert sample("serve_requests_total", status="ok")["value"] == len(oks)
    assert sample("serve_admitted_total")["value"] == len(oks)
    # Every ok record contributed one observation per stage histogram, and
    # the histogram sums equal the record-stream sums. Single-pool traffic
    # lands under the phase="mono" label (the phase-disaggregated pools
    # observe phase1/phase2 children instead).
    for metric, field in (("serve_queue_wait_ms", "queue_wait_ms"),
                          ("serve_run_ms", "run_ms"),
                          ("serve_request_total_ms", "total_ms")):
        s = sample(metric, phase="mono")
        assert s["count"] == len(oks)
        assert s["sum"] == pytest.approx(sum(r[field] for r in oks))
    occ = sample("serve_batch_occupancy", phase="mono")
    assert occ["count"] == summary["n_batches"]
    assert occ["sum"] == pytest.approx(
        summary["mean_batch_occupancy"] * summary["n_batches"])
    # Terminal gauges: everything resolved, nothing left waiting.
    assert sample("serve_queue_depth")["value"] == 0
    assert sample("serve_outstanding_requests")["value"] == 0
    # Spans: one serve.batch span pair per dispatched batch.
    batch_spans = [e for e in spans_mod.events()
                   if e["event"] == "span_end" and e["name"] == "serve.batch"]
    assert len(batch_spans) >= summary["n_batches"]


def test_serve_summary_percentiles_reconcile_within_one_bucket(tiny_pipe):
    """The ISSUE 3 acceptance criterion: the registry histogram's p50/p95
    agree with the summary's raw-list percentiles within one bucket."""
    reg = metrics_mod.registry()
    reg.reset()
    summary = _serve_fixture(tiny_pipe)[-1]
    fam = reg.get("serve_request_total_ms")
    hist = fam.labels(phase="mono")
    for q, raw in ((0.5, summary["p50_ms"]), (0.95, summary["p95_ms"])):
        est = hist.quantile(q)
        assert abs(hist.bucket_index(est) - hist.bucket_index(raw)) <= 1, \
            f"q={q}: histogram {est} vs summary {raw}"


def test_serve_reject_kinds_counted(tiny_pipe):
    from tests.test_serve import _fake_serve, _req

    reg = metrics_mod.registry()
    reg.reset()
    reqs = [_req("dup"), _req("dup"),                    # duplicate id
            _req("bad", steps=4, gate=9)]                # invalid gate spec
    recs = _fake_serve(tiny_pipe, reqs, max_batch=4, max_wait_ms=1.0)
    by = {}
    for r in recs:
        by.setdefault(r["status"], []).append(r)
    assert len(by["rejected"]) == 2
    snap = reg.snapshot()["serve_admission_rejects_total"]["samples"]
    # reset() zeroes in place but keeps label children registered by
    # earlier tests (e.g. queue_full), so filter the zero-valued ones.
    kinds = {s["labels"]["kind"]: s["value"] for s in snap if s["value"]}
    assert kinds == {"duplicate_id": 1, "invalid_spec": 1}


def test_program_cache_events_mirrored_to_registry():
    from p2p_tpu.serve import ProgramCache

    reg = metrics_mod.registry()
    reg.reset()
    c = ProgramCache(capacity=2)
    c.get("a", lambda: "A")
    c.get("a", lambda: "A2")
    c.get("b", lambda: "B")
    c.get("c", lambda: "C")                  # evicts a
    snap = reg.snapshot()["serve_program_cache_events_total"]["samples"]
    # The cache registers quarantine/build_retry children up front (and
    # reset() keeps children registered by earlier tests): compare only
    # the events that actually fired.
    events = {s["labels"]["event"]: s["value"] for s in snap if s["value"]}
    assert events == {"hit": 1, "miss": 3, "evict": 1}
    # Build time recorded per miss.
    compile_ms = reg.snapshot()["compile_ms"]["samples"]
    assert sum(s["count"] for s in compile_ms) == 3


# ---------------------------------------------------------------------------
# Device channel + the disabled-mode identity proof
# ---------------------------------------------------------------------------


def test_step_collector_phase_timing_and_events():
    reg = metrics_mod.Registry()
    col = obs_device.StepCollector(reg)
    col("step", 0, "phase1")
    col("step", 1, "phase1")
    col("step", 1, "phase1")     # duplicate delivery: no new delta
    col("step", 0, "phase2")     # phase change: timeline restarts
    col("step", 1, "phase2")
    col("invert.inner_steps", 7.0, None)
    snap = reg.snapshot()
    steps = {s["labels"]["phase"]: s["value"]
             for s in snap["sampler_steps_total"]["samples"]}
    assert steps == {"phase1": 3, "phase2": 2}
    ms = {s["labels"]["phase"]: s["count"]
          for s in snap["sampler_step_ms"]["samples"]}
    assert ms == {"phase1": 1, "phase2": 1}
    ev = snap["host_event_value"]["samples"][0]
    assert ev["labels"]["tag"] == "invert.inner_steps" and ev["count"] == 1


def test_step_collector_rearms_across_runs():
    """A multi-run session (seed sweep, bench repeats) restarts step indices
    at 0 under ONE collector: the timeline must re-arm per run, or every
    run after the first silently drops out of the ms/step histogram."""
    reg = metrics_mod.Registry()
    col = obs_device.StepCollector(reg)
    for _ in range(3):               # three runs of 0..2
        for s in range(3):
            col("step", s, "phase1")
    fam = reg.get("sampler_step_ms")
    # 2 deltas per run x 3 runs — not just the first run's 2.
    assert fam.labels(phase="phase1").count == 6
    assert reg.get("sampler_steps_total").labels(phase="phase1").value == 9


def test_metrics_only_emission_bypasses_stale_reporter():
    """A metrics-only program (report=False) must not feed the progress
    surfaces: nothing clears the module-level reporter between runs, so a
    stale one from an earlier progress run would otherwise print garbled
    lines during a later quiet-but-instrumented run."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.utils import progress

    reported, sunk = [], []
    progress.set_active(lambda s: reported.append(int(s)))
    progress.set_obs_sink(lambda tag, v, phase: sunk.append((tag, v, phase)))
    try:
        @jax.jit
        def f(x):
            def body(c, i):
                progress.emit_step(True, i, phase="phase1", report=False)
                return c + 1.0, None
            return jax.lax.scan(body, x, jnp.arange(3))[0]

        np.asarray(f(jnp.float32(0.0)))
        jax.effects_barrier()
    finally:
        progress.set_active(None)
        progress.set_obs_sink(None)
    assert reported == []                       # reporter stayed silent
    assert sorted(v for _, v, _ in sunk) == [0, 1, 2]
    assert all(p == "phase1" for _, _, p in sunk)


def test_poisoned_batch_occupancy_reconciles_with_summary(tiny_pipe):
    """Occupancy is observed on success only, next to the summary's list —
    a poisoned batch (re-dispatched lane-by-lane) must leave histogram
    count == n_batches and sum == mean * n."""
    from tests.test_serve import _fake_serve, _req

    reg = metrics_mod.registry()
    reg.reset()
    reqs = [_req(f"p{i}") for i in range(4)]
    recs = _fake_serve(tiny_pipe, reqs, poison={"p2"}, max_batch=4,
                       max_wait_ms=1.0)
    summary = recs[-1]
    assert summary["counts"]["error"] == 1      # the poisoned lane fails alone
    occ = reg.get("serve_batch_occupancy").labels(phase="mono")
    assert occ.count == summary["n_batches"]
    assert occ.sum == pytest.approx(
        summary["mean_batch_occupancy"] * summary["n_batches"])
    assert reg.get("serve_isolation_retries_total").value == 4


def test_sample_device_memory_never_raises():
    # CPU backends expose no memory_stats — must be a silent {} not a crash.
    out = obs_device.sample_device_memory(metrics_mod.Registry())
    assert isinstance(out, dict)


def test_metrics_disabled_adds_nothing_to_the_program():
    """The ISSUE 3 jaxpr-identity discipline, end to end on the sampler
    scan: with progress AND metrics off the compiled HLO carries no host
    callback (identical to the pre-telemetry program, which had no other
    ingredient); metrics alone traces it in."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.utils import progress

    def make(progress_on, metrics_on):
        def f(x):
            def body(c, i):
                progress.emit_step(progress_on or metrics_on, i,
                                   phase="phase1")
                return c * 1.5, None
            out, _ = jax.lax.scan(body, x, jnp.arange(3))
            return out
        # The lowered StableHLO, not the compiled text: the latter carries
        # the call site's line numbers in its stack-frame tables.
        return jax.jit(f).lower(jnp.float32(1.0)).as_text()

    off = make(False, False)
    assert "custom_call" not in off
    assert "custom_call" in make(False, True)
    # And the fully-disabled text is identical whichever flag is off — the
    # phase tag is host-side only and can't leak into the disabled program.
    assert off == make(False, False)


def test_sampler_outputs_bitwise_identical_with_metrics_enabled(tiny_pipe):
    import jax

    from p2p_tpu.engine.sampler import text2image

    kw = dict(num_steps=3, rng=jax.random.PRNGKey(11))
    base, xt0, _ = text2image(tiny_pipe, ["a cat"], None, **kw)
    metrics_mod.registry().reset()
    with obs_device.instrument():
        inst, xt1, _ = text2image(tiny_pipe, ["a cat"], None, metrics=True,
                                  **kw)
        inst = np.asarray(inst)
    assert np.array_equal(np.asarray(base), inst)
    assert np.array_equal(np.asarray(xt0), np.asarray(xt1))
    snap = metrics_mod.registry().snapshot()
    steps = sum(s["value"]
                for s in snap["sampler_steps_total"]["samples"])
    assert steps == 3                       # every scan step reported once
    assert snap["sampler_gate_step"]["samples"][0]["value"] == 3  # ungated
    assert snap["sampler_cfg_batch"]["samples"][0]["value"] == 2  # 2B, B=1


def test_gated_sampler_reports_both_phases(tiny_pipe):
    import jax

    from p2p_tpu.engine.sampler import text2image

    metrics_mod.registry().reset()
    with obs_device.instrument():
        img, _, _ = text2image(tiny_pipe, ["a cat"], None, num_steps=4,
                               rng=jax.random.PRNGKey(0), gate=2,
                               metrics=True)
        np.asarray(img)
    snap = metrics_mod.registry().snapshot()
    steps = {s["labels"]["phase"]: s["value"]
             for s in snap["sampler_steps_total"]["samples"]}
    assert steps == {"phase1": 2, "phase2": 2}
    assert snap["sampler_gate_step"]["samples"][0]["value"] == 2


def test_invert_emits_inner_step_events(tiny_pipe):
    from p2p_tpu.engine.inversion import invert

    img = np.random.RandomState(0).randint(
        0, 256, (tiny_pipe.config.image_size,
                 tiny_pipe.config.image_size, 3)).astype(np.uint8)
    metrics_mod.registry().reset()
    with obs_device.instrument():
        invert(tiny_pipe, img, "a cat", num_steps=2, num_inner_steps=2,
               metrics=True)
    snap = metrics_mod.registry().snapshot()
    ev = {s["labels"]["tag"]: s for s in snap["host_event_value"]["samples"]}
    # One inner-steps event per outer null-text step.
    assert ev["invert.inner_steps"]["count"] == 2
    # reset() zeroes children in place (it must not orphan held references),
    # so zero-valued families from earlier tests legitimately linger in the
    # snapshot — only nonzero phases belong to THIS run.
    phases = {s["labels"]["phase"]
              for s in snap["sampler_steps_total"]["samples"]
              if s["value"] > 0}
    assert phases == {"invert", "null_text"}
