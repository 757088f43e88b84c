"""The cost observatory (ISSUE 14): cost-card extraction behind the
API-drift guard, exact MFU/roofline arithmetic on fake peaks, the frozen
canonical budgets (clean tree passes, a seeded perturbation fails by
program name), the serve CostScope's disabled-mode parity + build/warm
compile split, per-device memory sampling, the per-site attention
TraceAnnotations, and the perfscope headline reproduction of the PERF.md
arithmetic from recorded artifacts alone.
"""

import importlib.util
import io
import json
import os
import re
import sys
import types

import numpy as np
import pytest

from p2p_tpu.obs import costmodel
from p2p_tpu.obs import device as obs_device
from p2p_tpu.obs import metrics as metrics_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Extraction: the dict-vs-list API-drift guard + cost cards
# ---------------------------------------------------------------------------


class _FakeCompiled:
    """Stub over the jax Compiled surface, parameterized by API shape."""

    def __init__(self, shape="dict", flops=2.0e9, bytes_accessed=1.0e8):
        self.shape = shape
        self.d = {"flops": flops, "bytes accessed": bytes_accessed,
                  "transcendentals": 7.0}

    def cost_analysis(self):
        if self.shape == "dict":
            return dict(self.d)
        return None

    def memory_analysis(self):
        if self.shape == "raises":
            raise RuntimeError("no memory analysis")
        return types.SimpleNamespace(
            argument_size_in_bytes=1000, output_size_in_bytes=200,
            temp_size_in_bytes=300, alias_size_in_bytes=0,
            generated_code_size_in_bytes=50,
            serialized_hlo_proto=b"\xff must never leak")


def test_cost_analysis_dict_is_a_dict_or_empty():
    want = {"flops": 2.0e9, "bytes accessed": 1.0e8, "transcendentals": 7.0}
    assert costmodel.cost_analysis_dict(_FakeCompiled("dict")) == want
    assert costmodel.cost_analysis_dict(_FakeCompiled("none")) == {}


def test_card_from_compiled_and_serializable():
    card = costmodel.card_from_compiled(_FakeCompiled(), "p", build_ms=5.0,
                                        warm_ms=2.0)
    assert card.flops == 2.0e9 and card.bytes_accessed == 1.0e8
    assert card.argument_bytes == 1000 and card.generated_code_bytes == 50
    assert card.peak_bytes == 1000 + 200 + 300 + 50
    assert card.arith_intensity == pytest.approx(20.0)
    d = card.to_dict()
    json.dumps(d)                    # the HLO proto must never leak in
    assert d["peak_bytes"] == card.peak_bytes
    assert d["build_ms"] == 5.0 and d["warm_ms"] == 2.0
    # A backend with no analyses yields an all-zero (but valid) card.
    empty = costmodel.card_from_compiled(_FakeCompiled("raises"), "q")
    assert empty.flops == 0.0 and empty.peak_bytes == 0


def test_card_from_real_compiled_matches_analytic_matmul():
    import jax
    import jax.numpy as jnp

    n = 128
    f = jax.jit(lambda a, b: a @ b)
    low = f.lower(jnp.zeros((n, n), jnp.float32),
                  jnp.zeros((n, n), jnp.float32))
    card = costmodel.card_from_compiled(low.compile(), "matmul")
    assert card.flops == pytest.approx(2 * n ** 3, rel=0.05)
    assert card.bytes_accessed >= 3 * n * n * 4     # 2 reads + 1 write
    assert card.argument_bytes == 2 * n * n * 4


# ---------------------------------------------------------------------------
# Peaks + roofline/MFU arithmetic (exact on fake peaks)
# ---------------------------------------------------------------------------

FAKE = costmodel.Peaks(flops_per_s=100e12, bytes_per_s=1e12,
                       platform="fake", source="fake")


def test_roofline_classification_and_prediction_exact():
    assert FAKE.ridge == pytest.approx(100.0)
    # Compute-bound: intensity 200 > ridge 100.
    r = costmodel.roofline(2e12, 1e10, FAKE)
    assert r["bound"] == "compute"
    assert r["arith_intensity"] == pytest.approx(200.0)
    assert r["compute_ms"] == pytest.approx(20.0)
    assert r["memory_ms"] == pytest.approx(10.0)
    assert r["predicted_ms"] == pytest.approx(20.0)
    # Bandwidth-bound: intensity 10 < ridge.
    r = costmodel.roofline(1e12, 1e11, FAKE)
    assert r["bound"] == "bandwidth"
    assert r["predicted_ms"] == pytest.approx(100.0)
    # devices=4 quarters both times.
    r4 = costmodel.roofline(1e12, 1e11, FAKE, devices=4)
    assert r4["predicted_ms"] == pytest.approx(25.0)


def test_mfu_pct_is_the_perf_md_formula():
    # 2e12 flops in 40 ms on a 100 TF/s peak: 50 TF/s = 50% MFU.
    assert costmodel.mfu_pct(2e12, 40.0, FAKE) == pytest.approx(50.0)
    assert costmodel.mfu_pct(2e12, 40.0, FAKE, devices=2) == \
        pytest.approx(25.0)
    # Unusable inputs (zero-timer rehearsal runs) → None, never a crash.
    assert costmodel.mfu_pct(2e12, 0.0, FAKE) is None
    assert costmodel.mfu_pct(0.0, 40.0, FAKE) is None


def test_platform_peak_table_and_detection():
    v5e = costmodel.lookup_peaks("TPU v5 lite")
    assert v5e is not None and v5e.source == "datasheet"
    assert v5e.flops_per_s == pytest.approx(197e12)
    assert v5e.bytes_per_s == pytest.approx(819e9)
    assert costmodel.lookup_peaks("warp drive") is None
    # CPU host: calibrated microbenchmark peaks, cached per process.
    peaks = costmodel.detect_peaks()
    assert peaks.source == "calibrated"
    assert peaks.flops_per_s > 0 and peaks.bytes_per_s > 0
    assert costmodel.detect_peaks() is peaks       # cached
    # A listed accelerator gets its datasheet row; an unlisted one is an
    # error naming the device_kind, never calibration under its label.
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert costmodel.detect_peaks(chip) is v5e
    odd = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        costmodel.detect_peaks(odd)


# ---------------------------------------------------------------------------
# Frozen budgets: clean tree passes, perturbation fails BY NAME
# ---------------------------------------------------------------------------


def _budget_doc(**programs):
    return {"rtol": 0.25, "programs": programs}


def test_check_budgets_clean_and_verdict_flip():
    cards = {"sweep/phase2/b1": {"flops": 2.0e9, "bytes_accessed": 1.0e8},
             "sweep/b1": {"flops": 3.0e9, "bytes_accessed": 2.0e8}}
    clean = _budget_doc(**{k: dict(v) for k, v in cards.items()})
    assert all(v.ok for v in costmodel.check_budgets(cards, clean))
    # The acceptance drill: a silently doubled phase-2 bytes-accessed must
    # fail, and the verdict must NAME the program.
    doubled = {**cards, "sweep/phase2/b1": {"flops": 2.0e9,
                                            "bytes_accessed": 2.0e8}}
    verdicts = costmodel.check_budgets(doubled, clean)
    bad = [v for v in verdicts if not v.ok]
    assert len(bad) == 1
    assert bad[0].program == "sweep/phase2/b1"
    assert bad[0].field == "bytes_accessed"
    assert "2.00x" in bad[0].format()
    # Inside-tolerance drift passes (rtol 0.25).
    jitter = {**cards, "sweep/b1": {"flops": 3.3e9,
                                    "bytes_accessed": 2.0e8}}
    assert all(v.ok for v in costmodel.check_budgets(jitter, clean))


def test_check_budgets_flags_missing_and_unfrozen_programs():
    clean = _budget_doc(**{"sweep/b1": {"flops": 1.0, "bytes_accessed": 1.0}})
    # Canonical program vanished from the pass.
    verdicts = costmodel.check_budgets({}, clean)
    assert [v for v in verdicts if not v.ok][0].program == "sweep/b1"
    assert "missing" in verdicts[0].problem
    # New canonical program shipped without freezing its budget.
    verdicts = costmodel.check_budgets(
        {"sweep/b1": {"flops": 1.0, "bytes_accessed": 1.0},
         "sweep/new": {"flops": 5.0, "bytes_accessed": 5.0}}, clean)
    bad = [v for v in verdicts if not v.ok]
    assert bad and bad[0].program == "sweep/new"
    assert "no frozen budget" in bad[0].problem


def test_canonical_cards_hold_the_committed_budgets(tiny_pipe):
    """The clean-tree half of the cost_regression acceptance: the
    canonical programs' measured cards must hold the committed frozen
    budgets (the exact diff the default-on quality-gate leg runs)."""
    cards = costmodel.canonical_cost_cards(tiny_pipe)
    budgets = costmodel.load_budgets(
        os.path.join(REPO, costmodel.DEFAULT_BUDGETS))
    verdicts = costmodel.check_budgets(cards, budgets)
    assert all(v.ok for v in verdicts), [v.format() for v in verdicts
                                         if not v.ok]
    # Structural sanity the cards must carry: the phase-1 pool program
    # (2 of 3 steps, no VAE decode) is strictly cheaper than the whole
    # monolithic sweep, and everything costs something.
    assert 0 < cards["sweep/phase1/b1"]["flops"] < cards["sweep/b1"]["flops"]
    assert all(c["bytes_accessed"] > 0 for c in cards.values())
    # The kernel-bearing twin (ISSUE 16) is a canonical card in its own
    # right: frozen alongside the materialized sweep, never heavier on
    # bytes — in-tile editing removes the probs round-trip.
    assert 0 < cards["sweep/kernel/b1"]["bytes_accessed"] \
        <= cards["sweep/b1"]["bytes_accessed"]


# ---------------------------------------------------------------------------
# CostScope: exact dispatch math, artifacts, summary
# ---------------------------------------------------------------------------


def test_costscope_record_dispatch_and_artifacts():
    reg = metrics_mod.Registry()
    scope = costmodel.CostScope(peaks=FAKE, registry=reg)
    key = ("phase2", 3, "ddim", 2, 2)
    entry = scope.record_program(key, 4, _FakeCompiled(flops=2e12,
                                                       bytes_accessed=1e10),
                                 build_ms=100.0, warm_ms=20.0)
    assert entry["bound"] == "compute"
    assert entry["predicted_ms"] == pytest.approx(20.0)
    # No cost analysis ⇒ no card (never a confidently-zero-cost program).
    assert scope.record_program(("nocard",), 1,
                                _FakeCompiled("raises")) is None
    assert scope.dispatch(("nocard",), 1, run_ms=5.0) == {}
    # Dispatch at exactly 2x the predicted time → 50% MFU (compute-bound).
    attrs = scope.dispatch(key, 4, run_ms=40.0, lanes=4)
    assert attrs["predicted_ms"] == pytest.approx(20.0)
    assert attrs["mfu_pct"] == pytest.approx(50.0)
    # Unknown program (fake-runner harness) and zero-timer runs degrade.
    assert scope.dispatch(("other",), 4, run_ms=40.0) == {}
    assert "mfu_pct" not in scope.dispatch(key, 4, run_ms=0.0)
    progs = scope.programs()
    assert len(progs) == 1 and progs[0]["dispatches"] == 2
    assert progs[0]["mean_mfu_pct"] == pytest.approx(50.0)
    assert progs[0]["mean_run_ms"] == pytest.approx(20.0)  # (40 + 0) / 2
    buf = io.StringIO()
    assert scope.write_programs_jsonl(buf) == 1
    line = json.loads(buf.getvalue())
    assert line["flops"] == 2e12 and line["build_ms"] == 100.0
    summ = scope.summary()
    assert summ["n_programs"] == 1 and summ["n_dispatches"] == 2
    assert summ["mean_mfu_pct"] == pytest.approx(50.0)
    assert summ["peaks"]["source"] == "fake"
    # Registry families carry the card + MFU observations.
    snap = reg.snapshot()
    assert snap["cost_cards_total"]["samples"][0]["value"] == 1
    assert snap["cost_dispatch_mfu_pct"]["samples"][0]["count"] == 1


def test_program_label_compacts_treedef_parts():
    label = costmodel._program_label(("phase1", 3, "X" * 200), 4)
    assert label.endswith("@b4") and len(label) < 60
    # Distinct long parts stay distinct.
    other = costmodel._program_label(("phase1", 3, "Y" * 200), 4)
    assert label != other
    # And the same key is stable across calls.
    assert label == costmodel._program_label(("phase1", 3, "X" * 200), 4)


# ---------------------------------------------------------------------------
# Serve integration: disabled-mode parity, cost block, build/warm split
# ---------------------------------------------------------------------------


def _serve_cost_trace(tiny_pipe, scope, timer=None, flight=None):
    from p2p_tpu.serve import Request, serve_forever

    prompts = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
    reqs = [Request(request_id="c-gated", prompt=prompts[0],
                    target=prompts[1], mode="replace", steps=3, seed=42,
                    gate=0.5, arrival_ms=0.0),
            Request(request_id="c-plain", prompt=prompts[0], steps=3,
                    seed=7, arrival_ms=1.0)]
    kw = dict(max_batch=4, max_wait_ms=1.0, costscope=scope, flight=flight)
    if timer is not None:
        kw["timer"] = timer
    return list(serve_forever(tiny_pipe, reqs, **kw))


def test_serve_costscope_disabled_mode_parity_and_cost_block(tiny_pipe):
    """The ISSUE 14 disabled-mode contract: observatory off ⇒ records
    byte-identical — and ON, the per-request stream is STILL untouched
    (cost facts live only in the summary/metrics/artifacts)."""
    metrics_mod.registry().reset()
    base = _serve_cost_trace(tiny_pipe, None, timer=lambda: 0.0)
    scope = costmodel.CostScope(peaks=FAKE)
    on = _serve_cost_trace(tiny_pipe, scope, timer=lambda: 0.0)

    def stripped(recs):
        return json.dumps([{k: v for k, v in r.items() if k != "images"}
                           for r in recs if r["status"] != "summary"],
                          sort_keys=True)

    assert stripped(base) == stripped(on)
    imgs_a = {r["request_id"]: r["images"] for r in base
              if r["status"] == "ok"}
    imgs_b = {r["request_id"]: r["images"] for r in on
              if r["status"] == "ok"}
    assert all(np.array_equal(imgs_a[k], imgs_b[k]) for k in imgs_a)
    s_off = [r for r in base if r["status"] == "summary"][0]
    s_on = [r for r in on if r["status"] == "summary"][0]
    # The summary gains exactly the cost block, nothing else moves.
    assert set(s_on) - set(s_off) == {"cost"}
    cost = s_on["cost"]
    # Gated + plain traffic = the three canonical serve programs, each
    # carded at its miss and observed at its dispatch.
    assert cost["n_programs"] == 3
    assert cost["n_dispatches"] == 3
    assert all(p["flops"] > 0 and p["bytes_accessed"] > 0
               for p in cost["programs"])
    assert all(p["build_ms"] >= 0 and p["dispatches"] == 1
               for p in cost["programs"])
    # Zero-timer run: measured MFU is honestly absent, never garbage.
    assert cost["mean_mfu_pct"] is None
    # The miss lump decomposed: build vs warm, one observation per miss,
    # alongside the unchanged what="program" total.
    snap = metrics_mod.registry().snapshot()
    counts = {s["labels"].get("what"): s["count"]
              for s in snap["compile_ms"]["samples"] if s["count"]}
    assert counts["build"] == 3 and counts["warm"] == 3
    # what="program" lumps from BOTH runs (the off-run misses too) — the
    # split is additional decomposition, never a replacement.
    assert counts["program"] == 6


def test_serve_costscope_annotates_flight_run_segments(tiny_pipe):
    from p2p_tpu.obs.flight import FlightTracer

    metrics_mod.registry().reset()
    # Calibrated host peaks (not the 100 TF/s fake): the tiny programs'
    # real-wall MFU must survive the 2-decimal rounding as nonzero.
    scope = costmodel.CostScope()
    tracer = FlightTracer()
    recs = _serve_cost_trace(tiny_pipe, scope, flight=tracer)
    assert [r for r in recs if r["status"] == "ok"]
    runs = [s for r in tracer.records for s in r["segments"]
            if s["stage"] == "run"]
    assert runs
    # Every run segment carries the model prediction; real wall timer ⇒
    # measured MFU rides along too.
    assert all("predicted_ms" in s for s in runs)
    assert all(s["mfu_pct"] > 0 for s in runs)
    pools = {s["pool"] for s in runs}
    assert {"mono", "phase1", "phase2"} <= pools


# ---------------------------------------------------------------------------
# Per-device memory sampling (PR 9 convention)
# ---------------------------------------------------------------------------


def test_sample_device_memory_labels_every_device(monkeypatch):
    class _Dev:
        def __init__(self, i, stats):
            self.id = i
            self._stats = stats

        def memory_stats(self):
            if isinstance(self._stats, Exception):
                raise self._stats
            return self._stats

    devs = [_Dev(0, {"bytes_in_use": 100, "peak_bytes_in_use": 200}),
            _Dev(1, {"bytes_in_use": 300, "ignored": "str"}),
            _Dev(2, None),                       # CPU-style: no stats
            _Dev(3, RuntimeError("stuck"))]     # never an error
    fake_jax = types.SimpleNamespace(local_devices=lambda: devs)
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    reg = metrics_mod.Registry()
    out = obs_device.sample_device_memory(reg)
    assert out == {"0": {"bytes_in_use": 100, "peak_bytes_in_use": 200},
                   "1": {"bytes_in_use": 300}}
    samples = reg.snapshot()["device_memory_bytes"]["samples"]
    by = {(s["labels"]["device"], s["labels"]["stat"]): s["value"]
          for s in samples}
    assert by[("0", "bytes_in_use")] == 100.0
    assert by[("1", "bytes_in_use")] == 300.0
    assert ("2", "bytes_in_use") not in by


# ---------------------------------------------------------------------------
# Per-site attention TraceAnnotations
# ---------------------------------------------------------------------------


def test_cross_attn_sites_named_per_site_in_hlo(tiny_pipe):
    """Every cross-attention site carries its own named scope in the
    compiled HLO's op metadata — the per-site split a Perfetto trace (and
    ROADMAP item 1's schedule search) attributes step time with. One
    distinct name per site in the layout, for cross AND self sites."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.models.config import unet_layout
    from p2p_tpu.models.unet import apply_unet

    cfg = tiny_pipe.config
    layout = unet_layout(cfg.unet)
    x = jnp.zeros((2, cfg.latent_size, cfg.latent_size,
                   cfg.unet.in_channels))
    ctx = jnp.zeros((2, cfg.unet.context_len, cfg.unet.context_dim))
    fn = jax.jit(lambda p, x, c: apply_unet(p, cfg.unet, x, jnp.int32(0),
                                            c, layout=layout)[0])
    txt = fn.lower(tiny_pipe.unet_params, x, ctx).compile().as_text()
    cross = set(re.findall(r"cross_attn/[a-z]+\d+", txt))
    self_ = set(re.findall(r"self_attn/[a-z]+\d+", txt))
    n_cross = sum(1 for m in layout.metas if m.is_cross)
    n_self = sum(1 for m in layout.metas if not m.is_cross)
    assert len(cross) == n_cross
    assert len(self_) == n_self
    # Names encode the site identity the layout declares.
    for m in layout.metas:
        kind = "cross_attn" if m.is_cross else "self_attn"
        assert f"{kind}/{m.place}{m.layer_idx}" in (cross | self_)


# ---------------------------------------------------------------------------
# perfscope: the PERF.md headline from recorded artifacts alone
# ---------------------------------------------------------------------------


def _perfscope():
    spec = importlib.util.spec_from_file_location(
        "p2p_perfscope", os.path.join(REPO, "tools", "perfscope.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perfscope_headline_reproduces_perf_md_arithmetic():
    """The acceptance criterion: 89 TF/s ≈ 45% MFU at 40.75 ms/step,
    recomputed from the committed artifacts (tools/cost_budgets.json
    headline block + the platform peak table) — no hand arithmetic."""
    perfscope = _perfscope()
    budgets = costmodel.load_budgets(
        os.path.join(REPO, costmodel.DEFAULT_BUDGETS))
    h = perfscope.headline(budgets)
    assert round(h["tf_per_s"]) == 89
    assert round(h["mfu_pct"]) == 45
    assert h["measured_ms_per_step"] == pytest.approx(40.75)
    assert h["peak_tf_per_s"] == pytest.approx(197.0)
    rendered = perfscope.render_headline(h)
    assert "89.1 TF/s" in rendered and "45.2% MFU" in rendered
    with pytest.raises(ValueError, match="no peak-table entry"):
        perfscope.headline({"headline": {**budgets["headline"],
                                         "platform": "warp drive"}})


# ---------------------------------------------------------------------------
# The jaxcheck report's cost section
# ---------------------------------------------------------------------------


def test_report_cost_section_and_verdict(monkeypatch, tmp_path):
    from p2p_tpu.analysis import report as report_mod

    cards = {"sweep/b1": {"flops": 1.0e9, "bytes_accessed": 1.0e8,
                          "arith_intensity": 10.0}}
    monkeypatch.setattr(costmodel, "canonical_cost_cards",
                        lambda pipe=None, bucket=1: cards)
    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps(_budget_doc(
        **{"sweep/b1": {"flops": 1.0e9, "bytes_accessed": 1.0e8}})))
    rep = report_mod.run_cost_pass(budgets_path=str(budgets))
    assert rep["cost"]["ok"] is True
    # Perturbed frozen bytes → the section (and the rendered report)
    # fails, naming the program.
    budgets.write_text(json.dumps(_budget_doc(
        **{"sweep/b1": {"flops": 1.0e9, "bytes_accessed": 5.0e7}})))
    rep = report_mod.run_cost_pass(budgets_path=str(budgets))
    assert rep["cost"]["ok"] is False
    text = report_mod.render_text({"version": 2, "ok": False, **rep})
    assert "sweep/b1" in text and "FAILED" in text
    doc = report_mod.to_json_dict({"version": 2, "ok": False, **rep})
    json.dumps(doc)
    assert doc["cost"]["budget"][0]["program"] == "sweep/b1"


def test_quality_gate_cost_regression_flip(monkeypatch, tmp_path):
    """Gate-level verdict flip: the cost_regression leg passes against
    the committed budgets and fails by name against a seeded
    perturbation, using the gate's own check function (the canonical
    pass is monkeypatched — its real compile half is covered by
    test_canonical_cards_hold_the_committed_budgets)."""
    spec = importlib.util.spec_from_file_location(
        "p2p_quality_gate", os.path.join(REPO, "tools", "quality_gate.py"))
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    cards = {"sweep/phase2/b1": {"flops": 2.0e9, "bytes_accessed": 1.0e8}}
    monkeypatch.setattr(costmodel, "canonical_cost_cards",
                        lambda pipe=None, bucket=1: cards)
    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(_budget_doc(
        **{"sweep/phase2/b1": {"flops": 2.0e9, "bytes_accessed": 1.0e8}})))
    verdicts = gate._cost_regression(None, budgets_path=str(clean))
    assert all(v.ok for v in verdicts)
    seeded = tmp_path / "seeded.json"
    seeded.write_text(json.dumps(_budget_doc(
        **{"sweep/phase2/b1": {"flops": 2.0e9, "bytes_accessed": 5.0e7}})))
    verdicts = gate._cost_regression(None, budgets_path=str(seeded))
    bad = [v for v in verdicts if not v.ok]
    assert bad and bad[0].program == "sweep/phase2/b1"
