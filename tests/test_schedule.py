"""Per-site per-step reuse schedules (ISSUE 15).

The generalization contract, pinned from both ends:

- the UNIFORM table is the PR-1 gate: bitwise, with identical compile
  keys, pooling with plain gated traffic (one executor runs both: that
  they trace to one program is pinned in tests/test_sampler_programs.py);
- a NON-uniform table is one compiled program whose key is the table
  CONTENTS: one-cell differences split keys, identical tables loaded
  from different files pool, and the per-phase key projections keep
  phase-2 pooling across schedules that differ only before the boundary;
- the committed search artifact stays inside the golden drift budget and
  its partial-site cache sizes/spills correctly across the hand-off.
"""

import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.controllers import factory
from p2p_tpu.engine import reuse as R
from p2p_tpu.engine.sampler import encode_prompts, resolve_reuse, text2image
from p2p_tpu.models import TINY
from p2p_tpu.models.config import unet_layout
from p2p_tpu.parallel import seed_latents
from p2p_tpu.parallel.sweep import sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "tools", "schedules", "default_v1.json")
PROMPTS = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
STEPS = 8
GATE = 4


def _layout():
    return unet_layout(TINY.unet)


def _ctrl(tokenizer, steps=STEPS):
    return factory.attention_replace(
        PROMPTS, steps, cross_replace_steps=0.4, self_replace_steps=0.25,
        tokenizer=tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)


def _uniform(gate=GATE, steps=STEPS):
    return R.ReuseSchedule.uniform(gate, steps, _layout())


# ---------------------------------------------------------------------------
# Spec validation + resolution
# ---------------------------------------------------------------------------


def test_validate_spec_rejects_bad_shapes():
    for bad, match in [
        ({"bogus": 1}, "unknown schedule spec key"),
        ({"version": 2}, "version"),
        ({"cfg_gate": "half"}, "cfg_gate"),
        ({"cross": {"nonsense": 0.5}}, "invalid site key"),
        ({"cross": {"self_attn/down0": 0.5}}, "other kind"),
        ({"self": {"*": 1.5}}, "outside"),
        ({"self": {"*": 0}}, ">= 1"),
        ([1, 2], "JSON object"),
    ]:
        with pytest.raises(ValueError, match=match):
            R.validate_spec(bad)


def test_resolve_defaults_and_per_site():
    lay = _layout()
    # cfg_gate alone IS the uniform gate (cross default to the gate, self
    # to never): the spec {"cfg_gate": g} must normalize onto gate=g.
    sched = R.resolve_schedule({"cfg_gate": 0.5}, lay, STEPS, None)
    assert sched.uniform_gate == GATE
    # Per-site override + kind default.
    sched = R.resolve_schedule(
        {"cfg_gate": GATE, "cross": {"*": GATE, "cross_attn/mid5": 2},
         "self": {"*": 6}}, lay, STEPS, None)
    assert sched.uniform_gate is None
    names = R.site_names(lay, "cross")
    assert sched.cross[names.index("cross_attn/mid5")] == 2
    assert all(r == 6 for r in sched.selfa)
    # Site names belonging to ANOTHER model's layout are inapplicable, not
    # an error — one committed artifact serves several models.
    sched2 = R.resolve_schedule(
        {"cfg_gate": GATE, "cross": {"cross_attn/down99": 1}}, lay, STEPS,
        None)
    assert sched2.uniform_gate == GATE
    # But a resolved table for the wrong scan length is a hard error.
    with pytest.raises(ValueError, match="-step scan"):
        R.resolve_schedule(_uniform(steps=STEPS), lay, STEPS + 1, None)
    # resolve_gate boundary discipline: a fraction rounding outside
    # [1, S] is a rejected typo, never a silent clamp (gate=0.05 at
    # steps=4 raises too).
    with pytest.raises(ValueError, match="outside"):
        R.resolve_schedule({"cfg_gate": 0.05}, lay, 4, None)
    with pytest.raises(ValueError, match="outside"):
        R.resolve_schedule({"cfg_gate": 2, "cross": {"*": 0.05}}, lay, 4,
                           None)


def test_resolve_reuse_mutual_exclusion_and_nulltext():
    lay = _layout()
    with pytest.raises(ValueError, match="mutually exclusive"):
        resolve_reuse(0.5, {"cfg_gate": 0.5}, lay, STEPS, None)
    # Non-uniform schedule + null-text embeddings rejected at text2image.
    from tests.test_golden import _pipe

    pipe = _pipe(TINY)
    ups = jnp.zeros((STEPS, 1, TINY.text.max_length, TINY.unet.context_dim))
    with pytest.raises(ValueError, match="null-text"):
        text2image(pipe, PROMPTS[:1], None, num_steps=STEPS,
                   uncond_embeddings=ups,
                   schedule={"cfg_gate": GATE, "self": {"*": 6}})


def test_key_roundtrip_and_projections():
    sched = R.ReuseSchedule(steps=8, cfg_gate=4, cross=(2, 4, 4, 8, 4, 4, 6),
                            selfa=(8,) * 7)
    assert R.ReuseSchedule.from_key(sched.key()) == sched
    p1 = R.phase1_view(sched)
    p2 = R.phase2_view(sched)
    # Phase 1 collapses everything at/past the gate (but keeps leaf
    # presence: 6 -> 4, 8 stays 8); phase 2 collapses everything before.
    assert p1.cross == (2, 4, 4, 8, 4, 4, 4)
    assert p2.cross == (4, 4, 4, 8, 4, 4, 6)
    # The views preserve the ever-cached leaf set — the hand-off carry is
    # structurally identical whichever view built the program.
    lay = _layout()
    assert R.cached_sites(lay, p1) == R.cached_sites(lay, sched)
    assert R.cached_sites(lay, p2) == R.cached_sites(lay, sched)


# ---------------------------------------------------------------------------
# Segmentation + cache sizing (the AttnCache partial-site satellite)
# ---------------------------------------------------------------------------


def test_segments_modes():
    lay = _layout()
    names = R.site_names(lay, "cross")
    spec = {"cfg_gate": 4, "cross": {"*": 4, names[0]: 2},
            "self": {"*": 6}}
    sched = R.resolve_schedule(spec, lay, STEPS, None)
    segs1 = R.segments(lay, R.phase1_view(sched), phase=1)
    assert [(s.start, s.stop) for s in segs1] == [(0, 2), (2, 4)]
    # The early cross site stores FULL batch before its flip, then uses;
    # the at-gate cross sites store the cond half throughout phase 1;
    # self sites (flipping in phase 2) own a leaf and store cond-half too.
    i_early = next(i for i, m in enumerate(lay.metas)
                   if m.is_cross and R.site_name(m) == names[0])
    assert segs1[0].plan[i_early] == R.MODE_STORE_ALL
    assert segs1[1].plan[i_early] == R.MODE_USE
    other_cross = next(i for i, m in enumerate(lay.metas)
                       if m.is_cross and R.site_name(m) != names[0])
    assert all(s.plan[other_cross] == R.MODE_STORE for s in segs1)
    segs2 = R.segments(lay, R.phase2_view(sched), phase=2)
    assert [(s.start, s.stop) for s in segs2] == [(4, 6), (6, 8)]
    i_self = next(i for i, m in enumerate(lay.metas) if not m.is_cross)
    assert segs2[0].plan[i_self] == R.MODE_STORE_ALL
    assert segs2[1].plan[i_self] == R.MODE_USE
    assert all(s.plan[i_early] == R.MODE_USE for s in segs2)


def test_partial_site_cache_sizing():
    """AttnCache sizing for partial-site caching: only ever-reused sites
    own leaves; sites reused while CFG is live hold the doubled batch in
    phase 1 and slice to the cond half at the boundary."""
    lay = _layout()
    names = R.site_names(lay, "cross")
    sched = R.resolve_schedule(
        {"cfg_gate": 4, "cross": {"*": None, names[0]: 2, names[1]: 4},
         "self": {"*": None, R.site_names(lay, "self")[0]: 6}},
        lay, STEPS, None)
    b = 2
    cache1 = R.init_schedule_cache(lay, sched, b, phase=1,
                                   dtype=jnp.float32)
    assert len(cache1) == 3          # 2 cross + 1 self ever cached
    cached = R.cached_sites(lay, sched)
    # Leaves ride in layout CALL order; batch is 2B only for the site
    # reused while CFG is live (names[0] at step 2 < cfg_gate 4).
    for leaf, i in zip(cache1, cached):
        m = lay.metas[i]
        want_b = 2 * b if R.site_name(m) == names[0] else b
        assert leaf.shape == (want_b, m.pixels, m.channels), R.site_name(m)
    sliced = R.slice_cache_to_cond(lay, sched, cache1, b)
    assert all(leaf.shape[0] == b for leaf in sliced)
    cache2 = R.init_schedule_cache(lay, sched, b, phase=2,
                                   dtype=jnp.float32)
    assert [leaf.shape for leaf in cache2] == [leaf.shape
                                               for leaf in sliced]
    # 5-tuple layouts (no channel info) cannot size the cache — loud error.
    from p2p_tpu.controllers.base import build_layout

    lay5 = build_layout([("down", True, 8, 2, 16)])
    s5 = R.ReuseSchedule(steps=4, cfg_gate=2, cross=(2,), selfa=())
    with pytest.raises(ValueError, match="channel"):
        R.init_schedule_cache(lay5, s5, 1, phase=2, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# The uniform table is the gate
# ---------------------------------------------------------------------------


def test_uniform_schedule_normalizes_to_gate_bitwise(tiny_pipe):
    kw = dict(num_steps=STEPS, rng=jax.random.PRNGKey(7))
    ctrl = _ctrl(tiny_pipe.tokenizer)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        img_g, xt_g, _ = text2image(tiny_pipe, PROMPTS, ctrl, gate=GATE,
                                    **kw)
        img_u, xt_u, _ = text2image(tiny_pipe, PROMPTS, ctrl,
                                    schedule={"cfg_gate": GATE}, **kw)
    assert np.array_equal(np.asarray(img_g), np.asarray(img_u))
    assert np.array_equal(np.asarray(xt_g), np.asarray(xt_u))


# ---------------------------------------------------------------------------
# Committed artifact: drift budget + structure
# ---------------------------------------------------------------------------


def test_committed_artifact_is_valid_and_nonuniform():
    with open(ARTIFACT) as f:
        spec = json.load(f)
    R.validate_spec(spec)
    lay = _layout()
    sched = R.resolve_schedule(spec, lay, STEPS, None)
    assert sched.uniform_gate is None, \
        "the committed artifact must be a genuine per-site schedule"
    counts = sched.sites_cached()
    assert counts["self"] >= 1 and counts["cross"] >= 1
    prov = spec.get("provenance") or {}
    assert prov.get("measured_speedup", 0) >= 1.5
    assert prov.get("measured_mse", 1) <= prov.get("drift_budget", 1e-2)


@pytest.mark.parametrize("scheduler,budget", [("ddim", 1e-2),
                                              ("dpm", 2e-2)])
def test_scheduled_drift_within_budget(tiny_pipe, scheduler, budget):
    """A representative non-uniform schedule stays inside the golden
    drift budget on the standard DDIM trajectory (the committed artifact
    itself is re-validated end to end by the quality gate's `schedule`
    leg). The DPM leg pins the executor across the multistep-state
    hand-off at a correspondingly looser bound — the higher-order solver
    amplifies the cached-feature perturbation, and the golden ≤1e-2
    budget is a DDIM-workload contract."""
    ctrl = _ctrl(tiny_pipe.tokenizer)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (1,) + x.shape), ctrl)
    cond = encode_prompts(tiny_pipe, PROMPTS)
    unc = encode_prompts(tiny_pipe, [""] * 2)
    ctx = jnp.concatenate([unc, cond], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(42), 1, 2,
                        tiny_pipe.latent_shape)
    spec = {"cfg_gate": GATE, "cross": {"*": GATE, "cross_attn/mid5": 2},
            "self": {"*": 6}}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, lat_base = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS,
                            scheduler=scheduler)
        _, lat_sched = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS,
                             scheduler=scheduler, schedule=spec)
    mse = float(((np.asarray(lat_sched, np.float64)
                  - np.asarray(lat_base, np.float64)) ** 2).mean())
    assert mse <= budget, mse


# ---------------------------------------------------------------------------
# Keys: pooling both directions, projections, serve parity
# ---------------------------------------------------------------------------


def _prep(tiny_pipe, **over):
    from p2p_tpu.serve.request import Request, prepare

    base = dict(request_id="s1", prompt=PROMPTS[0], target=PROMPTS[1],
                mode="replace", steps=4, seed=42)
    return prepare(Request(**{**base, **over}), tiny_pipe)


def test_schedule_key_completeness_both_directions(tiny_pipe, tmp_path):
    spec_a = {"cfg_gate": 2, "cross": {"*": 2, "cross_attn/down1": 1},
              "self": {"*": None}}
    # One site-step cell different: must NOT pool.
    spec_b = {"cfg_gate": 2, "cross": {"*": 2, "cross_attn/down3": 1},
              "self": {"*": None}}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pa = _prep(tiny_pipe, schedule=spec_a)
        pb = _prep(tiny_pipe, schedule=spec_b)
        assert pa.compile_key != pb.compile_key
        assert pa.content_key != pb.content_key
        assert pa.phase1_key != pb.phase1_key
        # ...but the difference is phase-1-only: phase-2 pools.
        assert pa.phase2_key == pb.phase2_key
        assert pa.phase2_batch_key == pb.phase2_batch_key

        # Identical tables loaded from different FILES must pool.
        for name, spec in (("a.json", spec_a), ("c.json", dict(spec_a))):
            with open(tmp_path / name, "w") as f:
                json.dump(spec, f)
        loaded = [R.load_spec(str(tmp_path / n)) for n in ("a.json",
                                                           "c.json")]
        pc, pd = (_prep(tiny_pipe, schedule=sp) for sp in loaded)
        assert pc.compile_key == pd.compile_key
        assert pc.content_key == pd.content_key

        # The uniform table pools with — and content-keys as — plain gate.
        pu = _prep(tiny_pipe, schedule={"cfg_gate": 0.5})
        pg = _prep(tiny_pipe, gate=0.5)
        assert pu.compile_key == pg.compile_key
        assert pu.content_key == pg.content_key
        assert pu.phase1_key == pg.phase1_key
        assert pu.phase2_key == pg.phase2_key
        assert pu.schedule is None


def test_analysis_sweeps_cover_schedule_field():
    from p2p_tpu.analysis.compile_key import (check_compile_key,
                                              check_content_key,
                                              check_phase_keys)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for fn in (check_compile_key, check_phase_keys, check_content_key):
            verdicts = fn(fields=["schedule"])
            assert verdicts and all(v.ok for v in verdicts), \
                [v.format() for v in verdicts if not v.ok]


def test_gate_and_schedule_are_schema_exclusive(tiny_pipe):
    with pytest.raises(ValueError, match="mutually exclusive"):
        _prep(tiny_pipe, gate=0.5, schedule={"cfg_gate": 0.5})


def test_scheduled_serve_parity_and_spill(tiny_pipe, tmp_path):
    """A scheduled request served through the two pools is bitwise the
    direct scheduled text2image — and its partial-site carry spills and
    reloads against the request-derived template (the crash-resume
    spec)."""
    from p2p_tpu.engine.sampler import carry_spec
    from p2p_tpu.serve import Request, serve_forever
    from p2p_tpu.serve.handoff import carry_template, load_carry, \
        spill_carry

    spec = {"cfg_gate": 2, "cross": {"*": 2, "cross_attn/down1": 1},
            "self": {"*": 3}}
    req = Request(request_id="sched-e2e", prompt=PROMPTS[0],
                  target=PROMPTS[1], mode="replace", steps=4, seed=42,
                  schedule=spec)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        recs = [r for r in serve_forever(tiny_pipe, [req], max_batch=4,
                                         max_wait_ms=1.0)
                if r["status"] == "ok"]
        assert len(recs) == 1 and "phases" in recs[0]
        # Same controller the serve path builds for this request (the
        # Request schema's default edit windows) — the shared factory.
        from p2p_tpu.cli import controller_from_opts

        ctrl = controller_from_opts(PROMPTS, tiny_pipe.tokenizer, 4,
                                    mode="replace", cross_steps=0.8,
                                    self_steps=0.4)
        want, _, _ = text2image(tiny_pipe, PROMPTS, ctrl, num_steps=4,
                                rng=jax.random.PRNGKey(42), schedule=spec)
        assert np.array_equal(recs[0]["images"], np.asarray(want))

        prep = _prep(tiny_pipe, schedule=spec)
        template = carry_template(tiny_pipe, prep)
        # The scheduled template's cache is the schedule's leaf set, not
        # the all-cross AttnCache.
        lay = _layout()
        assert len(template["carry"].cache) == \
            len(R.cached_sites(lay, prep.schedule))
        path = str(tmp_path / "carry.npz")
        spill_carry(template, path)
        loaded = load_carry(path, template)
        assert carry_spec(loaded) == carry_spec(template)
        # A schedule differing only in a phase-1 flip step shares the
        # carry STRUCTURE (that is the phase-2 pooling design), so its
        # template accepts the spill...
        same_leaves = _prep(tiny_pipe, schedule={"cfg_gate": 2,
                                                 "self": {"*": 3}})
        load_carry(path, carry_template(tiny_pipe, same_leaves))
        # ...but a schedule with a different LEAF SET (here: the uniform
        # gate, all-cross cache, no self leaves) must be refused.
        other = _prep(tiny_pipe, gate=0.5)
        with pytest.raises(ValueError, match="pinned spec|leaves"):
            load_carry(path, carry_template(tiny_pipe, other))


def test_cfg_alive_schedule_is_single_pool(tiny_pipe):
    # cfg_gate = S (CFG never drops) with cached sites: a real schedule,
    # but no phase boundary — it must take the monolithic serve path.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        prep = _prep(tiny_pipe, schedule={"cross": {"*": 3}})
    assert prep.schedule is not None
    assert not prep.gated
    assert prep.phase1_key is None and prep.phase2_key is None


# ---------------------------------------------------------------------------
# Window-conflict warning (generalized warn_gate_truncation)
# ---------------------------------------------------------------------------


def test_schedule_conflict_warns_once_naming_sites(tokenizer):
    lay = _layout()
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=0.9, self_replace_steps=0.25,
        tokenizer=tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)
    # Cross window ends late (0.9·(T+1) = 8); one cross site reuses at 3,
    # inside it. Self sites reuse at 6 — OUTSIDE the self window (2), so
    # they must NOT be named.
    sched = R.resolve_schedule(
        {"cfg_gate": None, "cross": {"*": None, "cross_attn/down1": 3},
         "self": {"*": 6}}, lay, STEPS, ctrl)
    R._warned_conflicts.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        offending = R.warn_schedule_conflicts(sched, lay, ctrl, STEPS)
    assert any("cross_attn/down1" in str(x.message) for x in w)
    assert offending and all(o.startswith("cross_attn/down1")
                             for o in offending)
    assert not any("self_attn" in o for o in offending)
    # Once: the identical conflict set does not re-warn.
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        R.warn_schedule_conflicts(sched, lay, ctrl, STEPS)
    assert not [x for x in w2 if "cross_attn/down1" in str(x.message)]


def test_store_controller_warns_even_without_edit_window():
    # A pure observability store (no edit → window 0) under a gated
    # schedule must still get the store-freeze warning, exactly as the
    # gate path surfaces it through warn_gate_truncation.
    lay = _layout()
    ctrl = factory.attention_store()
    sched = R.resolve_schedule({"cfg_gate": GATE}, lay, STEPS, ctrl)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        R.warn_schedule_conflicts(sched, lay, ctrl, STEPS)
    assert any("stops accumulating" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# Satellites: perfscope --sites + schedule_search smoke
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"p2p_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perfscope_sites_renders_recorded_trace(capsys):
    perfscope = _load_tool("perfscope")
    trace = os.path.join(REPO, "tests", "data", "site_trace_tiny.json")
    entries = perfscope.parse_site_trace(trace)
    lay = _layout()
    assert {e["site"] for e in entries} == \
        {R.site_name(m) for m in lay.metas}
    assert abs(sum(e["share"] for e in entries) - 1.0) < 1e-9
    assert all(e["slices"] == 4 for e in entries)   # 4 recorded steps
    # Shares ordered descending — the search consumes them biggest-first.
    shares = [e["share"] for e in entries]
    assert shares == sorted(shares, reverse=True)
    out = perfscope.render_sites(entries)
    assert "cross-attention share" in out
    # CLI path end to end (exit 0, table rendered).
    assert perfscope.main(["--sites", trace]) == 0
    assert "attention site(s)" in capsys.readouterr().out
    # A non-trace file is a loud usage error, not a zero table.
    with pytest.raises(ValueError, match="chrome-trace"):
        perfscope.parse_site_trace(os.path.join(REPO, "tools",
                                                "cost_budgets.json"))
    # A real trace with no site slices too (is this a DEVICE trace?).
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump({"traceEvents": [{"ph": "X", "name": "fusion.1",
                                    "dur": 5.0}]}, f)
    with pytest.raises(ValueError, match="no attention-site"):
        perfscope.parse_site_trace(f.name)
    os.unlink(f.name)


def test_perfscope_fuse_plan_ranks_and_feeds_kernel_config(tmp_path,
                                                           capsys):
    """ISSUE 16 satellite: --fuse-plan ranks sites by measured step-time
    share × materialized-map bytes and emits exactly the artifact
    KernelConfig.from_fuse_plan consumes."""
    from p2p_tpu.kernels import KernelConfig

    perfscope = _load_tool("perfscope")
    trace = os.path.join(REPO, "tests", "data", "site_trace_tiny.json")
    out = str(tmp_path / "fuse_plan.json")
    rc = perfscope.main(["--sites", trace, "--fuse-plan", out,
                         "--plan-config", "tiny"])
    assert rc == 0
    assert "wrote fuse plan" in capsys.readouterr().out
    with open(out) as f:
        plan = json.load(f)
    lay = _layout()
    assert {e["site"] for e in plan["fuse_order"]} == \
        {R.site_name(m) for m in lay.metas}
    scores = [e["score"] for e in plan["fuse_order"]]
    assert scores == sorted(scores, reverse=True)
    assert plan["dropped"] == []
    # P=256 self sites move the biggest map AND are hottest → fuse first.
    assert plan["fuse_order"][0]["site"].startswith("self_attn/")
    assert plan["fuse_order"][0]["map_bytes"] == 2 * 1 * 2 * 256 * 256 * 4
    # The artifact is directly consumable, prefix-take preserving rank.
    kc = KernelConfig.from_fuse_plan(out, take=3)
    assert kc.sites == tuple(e["site"] for e in plan["fuse_order"][:3])
    assert KernelConfig.from_fuse_plan(plan).sites == \
        tuple(e["site"] for e in plan["fuse_order"])
    # Unmeasured layout sites rank last at share 0 (explicitly marked);
    # trace sites the layout doesn't know are dropped LOUDLY.
    entries = perfscope.parse_site_trace(trace)
    partial = [e for e in entries if e["site"] != "self_attn/down0"]
    partial.append({"site": "self_attn/down99", "share": 0.5})
    plan2 = perfscope.fuse_plan(partial, config="tiny")
    tail = {e["site"]: e for e in plan2["fuse_order"]}
    assert not tail["self_attn/down0"]["measured"]
    assert tail["self_attn/down0"]["share"] == 0.0
    assert plan2["dropped"] == ["self_attn/down99"]
    assert "dropped" in perfscope.render_fuse_plan(plan2)
    # Honored-flags discipline: --fuse-plan without --sites is a usage
    # error; an unknown preset is a loud exit 2.
    with pytest.raises(SystemExit):
        perfscope.main(["--fuse-plan", out])
    assert perfscope.main(["--sites", trace, "--fuse-plan", out,
                           "--plan-config", "nope"]) == 2


def test_schedule_search_smoke(tmp_path, tiny_pipe):
    """Tiny-budget end-to-end search: measures the uniform baseline plus
    one relaxation, respects the eval cap, and emits a valid artifact
    with provenance."""
    search = _load_tool("schedule_search")
    out = str(tmp_path / "found.json")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rc = search.main(["--steps", "8", "--groups", "1", "--reps", "1",
                          "--max-evals", "2", "--gate-grid", "0.5",
                          "--grid", "0.62", "--out", out])
    assert rc == 0
    with open(out) as f:
        spec = json.load(f)
    R.validate_spec(spec)
    prov = spec["provenance"]
    assert prov["evals"] <= 2
    assert prov["uniform_gate_speedup"] > 0
    # The emitted spec must resolve on the real layout.
    R.resolve_schedule(spec, _layout(), 4, None)


def test_site_cost_shares_align_with_site_names():
    search = _load_tool("schedule_search")
    lay = _layout()
    shares = search.site_cost_shares(lay, batch=2)
    assert set(shares) == {R.site_name(m) for m in lay.metas}
    assert abs(sum(shares.values()) - 1.0) < 1e-9
