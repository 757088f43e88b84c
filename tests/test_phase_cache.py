"""Phase-gated sampling (ISSUE 1): cross-attention caching + CFG truncation.

Covers the three spec'd properties plus the program-structure acceptance
check:

(a) ``gate=T`` is bitwise-identical to the baseline sampler (the feature-off
    path compiles the exact pre-existing program);
(b) ``gate=0.5T`` latent drift vs the golden npz stays under threshold
    (with test_golden's foreign-platform fallback: when the in-session
    baseline itself disagrees with the npz — different BLAS/ISA than the
    pinning host — the drift is measured against the in-session baseline);
(c) ``gate='auto'`` resolves to ≥ the controller's cross/self edit-window
    end for every controller ``controllers.factory`` can build;
(d) the phase-2 scan body contains no uncond batch half (batch-dim walk over
    the jaxpr) and is a strictly smaller program than phase 1.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.controllers import factory
from p2p_tpu.controllers.base import controller_step_window
from p2p_tpu.engine.sampler import (
    _denoise_scan,
    encode_prompts,
    resolve_gate,
    text2image,
)
from p2p_tpu.models import TINY
from p2p_tpu.models.config import unet_layout
from p2p_tpu.ops import schedulers as sched_mod
from p2p_tpu.parallel import seed_latents, sweep

STEPS = 8
GATE = 4
PROMPTS = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "phase_gate.npz")

# ISSUE 1 target: ≤1e-2 golden-latent MSE at gate=0.5T. Measured 5.9e-3 on
# the pinning host (CPU f32) against a baseline latent variance of ~75.
MSE_THRESHOLD = 1e-2
# An ungated re-run that diverges this much from the npz is a different
# numeric platform, not a regression (same reasoning as test_golden's
# tolerance fallback) — the drift check then runs against the in-session
# baseline.
PLATFORM_TOL = 1e-3


def _ctrl(tokenizer, steps=STEPS, store=False):
    return factory.attention_replace(
        PROMPTS, steps, cross_replace_steps=0.4, self_replace_steps=0.25,
        tokenizer=tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length, store=store)


def _sweep_inputs(pipe):
    ctrl = _ctrl(pipe.tokenizer)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (1,) + x.shape), ctrl)
    cond = encode_prompts(pipe, PROMPTS)
    uncond = encode_prompts(pipe, [""] * len(PROMPTS))
    ctx = jnp.concatenate([uncond, cond], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(42), 1, len(PROMPTS),
                        pipe.latent_shape)
    return ctx, lats, ctrls


# ---------------------------------------------------------------------------
# (a) gate=T ≡ baseline, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheduler", ["ddim", "plms", "dpm"])
def test_gate_full_is_bitwise_identical(tiny_pipe, scheduler):
    ctrl = _ctrl(tiny_pipe.tokenizer)
    kw = dict(num_steps=STEPS, scheduler=scheduler,
              rng=jax.random.PRNGKey(7))
    img_base, xt_base, _ = text2image(tiny_pipe, PROMPTS, ctrl, **kw)
    # gate equal to the scan length (T for ddim/dpm, T+1 for plms) is the
    # feature-off path and must reproduce the baseline exactly.
    scan_len = STEPS + 1 if scheduler == "plms" else STEPS
    img_gate, xt_gate, _ = text2image(tiny_pipe, PROMPTS, ctrl, gate=scan_len,
                                      **kw)
    assert np.array_equal(np.asarray(img_base), np.asarray(img_gate))
    assert np.array_equal(np.asarray(xt_base), np.asarray(xt_gate))


def test_gate_full_sweep_latents_bitwise(tiny_pipe):
    ctx, lats, ctrls = _sweep_inputs(tiny_pipe)
    _, lat_base = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS)
    _, lat_gate = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS,
                        gate=STEPS)
    assert np.array_equal(np.asarray(lat_base), np.asarray(lat_gate))


# ---------------------------------------------------------------------------
# (b) gate=0.5T drift vs the golden latents
# ---------------------------------------------------------------------------


def test_gate_half_latent_mse_under_threshold(tiny_pipe):
    ctx, lats, ctrls = _sweep_inputs(tiny_pipe)
    _, lat_base = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS)
    _, lat_gate = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS,
                        gate=GATE)
    lat_base = np.asarray(lat_base, dtype=np.float64)
    lat_gate = np.asarray(lat_gate, dtype=np.float64)

    golden = np.load(GOLDEN)["latents_base"].astype(np.float64)
    assert golden.shape == lat_base.shape
    ref = golden
    if ((lat_base - golden) ** 2).mean() > PLATFORM_TOL:
        # Foreign numeric platform: the pinned baseline itself doesn't
        # reproduce here, so measure the gating drift against the
        # in-session baseline (the property under test is the drift the
        # *gate* introduces, not BLAS portability).
        ref = lat_base
    mse = ((lat_gate - ref) ** 2).mean()
    assert mse <= MSE_THRESHOLD, (
        f"gate={GATE}/{STEPS} latent MSE {mse:.4g} exceeds "
        f"{MSE_THRESHOLD} (baseline var {ref.var():.3g})")


# ---------------------------------------------------------------------------
# (c) gate='auto' never truncates inside an edit window
# ---------------------------------------------------------------------------


def _factory_controllers(tokenizer):
    """One controller per public factory constructor, with late windows so a
    too-early auto gate would be caught."""
    steps = STEPS
    kw = dict(cross_replace_steps=0.9, self_replace_steps=0.8,
              tokenizer=tokenizer, self_max_pixels=8 * 8,
              max_len=TINY.text.max_length)
    eq = np.ones((1, TINY.text.max_length), np.float32)
    lb = factory.local_blend(PROMPTS, ["burger", "lasagna"], tokenizer,
                             num_steps=steps, resolution=8,
                             max_len=TINY.text.max_length)
    yield "empty", factory.empty_control()
    yield "store", factory.attention_store()
    yield "spatial", factory.spatial_replace(steps, stop_inject=0.2)
    yield "replace", factory.attention_replace(PROMPTS, steps, **kw)
    yield "refine", factory.attention_refine(PROMPTS, steps, **kw)
    yield "reweight", factory.attention_reweight(PROMPTS, steps,
                                                 equalizer=eq, **kw)
    yield "replace_blend", factory.attention_replace(PROMPTS, steps,
                                                     local_blend=lb, **kw)
    yield "make_controller", factory.make_controller(
        PROMPTS, True, 0.9, 0.8, tokenizer, num_steps=steps,
        self_max_pixels=8 * 8)


def test_gate_auto_resolves_past_every_factory_window(tokenizer):
    for name, ctrl in _factory_controllers(tokenizer):
        window = controller_step_window(ctrl, STEPS)
        auto = resolve_gate("auto", STEPS, ctrl)
        assert auto >= window, (
            f"{name}: auto gate {auto} truncates inside the edit window "
            f"(ends {window})")
        assert 1 <= auto <= STEPS, (name, auto)


def test_controller_step_window_values(tokenizer):
    # Identity has no window; a 0.9/0.8 replace controller's window ends at
    # the cross schedule's support end (cross_alpha has T+1 entries, so
    # int(0.9·(T+1)) = 8 at T=8 — past the self window's int(0.8·8) = 6).
    assert controller_step_window(None, STEPS) == 0
    assert controller_step_window(factory.empty_control(), STEPS) == 0
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=0.9, self_replace_steps=0.8,
        tokenizer=tokenizer, max_len=TINY.text.max_length)
    assert controller_step_window(ctrl, STEPS) == 8
    sp = factory.spatial_replace(STEPS, stop_inject=0.25)
    assert controller_step_window(sp, STEPS) == 6  # (1-0.25)·8


# ---------------------------------------------------------------------------
# (d) phase-2 program: no uncond batch half, strictly smaller
# ---------------------------------------------------------------------------


def _all_eqns(jaxpr):
    """Every equation in a jaxpr, recursing into sub-jaxprs (scan/cond/pjit
    bodies), so shapes can't hide one nesting level down."""
    eqns = []
    for eqn in jaxpr.eqns:
        eqns.append(eqn)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                eqns.extend(_all_eqns(sub))
    return eqns


def _shapes(eqns):
    out = []
    for eqn in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
    return out


def test_phase2_scan_has_no_uncond_batch_half(tiny_pipe):
    b = len(PROMPTS)
    layout = unet_layout(TINY.unet)
    schedule = sched_mod.schedule_from_config(STEPS, TINY.scheduler,
                                              kind="ddim")
    ctrl = _ctrl(tiny_pipe.tokenizer)
    cond = encode_prompts(tiny_pipe, PROMPTS)
    uncond = encode_prompts(tiny_pipe, [""] * b)
    ctx = jnp.concatenate([uncond, cond], axis=0)
    lats = jnp.zeros((b,) + tiny_pipe.latent_shape)
    gs = jnp.float32(7.5)

    def run(ctx, lats, gs, gate):
        return _denoise_scan(tiny_pipe.unet_params, TINY, layout, schedule,
                             "ddim", ctx, lats, ctrl, gs, gate=gate)

    jaxpr = jax.make_jaxpr(lambda c, l, g: run(c, l, g, GATE))(ctx, lats, gs)
    scans = [e for e in _all_eqns(jaxpr.jaxpr) if e.primitive.name == "scan"]
    # Outermost: the phase-1 and phase-2 scans in order (recursion may also
    # surface nested scans; the two top-level ones come first).
    top = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(top) == 2, f"expected a two-phase scan, got {len(top)}"
    body1 = _all_eqns(top[0].params["jaxpr"].jaxpr)
    body2 = _all_eqns(top[1].params["jaxpr"].jaxpr)

    latent_hw = tiny_pipe.latent_shape[0]

    def doubled(shapes):
        # Any 4-D feature map with the CFG-doubled batch (2B, h, w, ·) or a
        # 3-D token-major tensor (2B, P, C): the uncond half's footprint.
        return [s for s in shapes
                if len(s) >= 3 and s[0] == 2 * b
                and (len(s) == 4 or (len(s) == 3 and s[1] <= latent_hw ** 2))]

    assert doubled(_shapes(body1)), "detector is vacuous: phase 1 must " \
                                    "carry the CFG-doubled batch"
    assert not doubled(_shapes(body2)), (
        "phase-2 scan still carries uncond-batch-half tensors: "
        f"{sorted(set(doubled(_shapes(body2))))[:5]}")
    # Program-size assertion: dropping the uncond half + serving cross
    # attention from the cache must shrink the phase-2 step body.
    assert len(body2) < len(body1), (len(body2), len(body1))


def test_apply_unet_use_sites_read_their_cache(tiny_pipe):
    """A ``use`` site of the plan returns its cached tensor and leaves the
    cache as it was. A controller is accepted beside ``use`` sites (a
    schedule may reuse a site under CFG while the controller acts on the
    others), and its cross edit has no effect at a site that is ``use``:
    no probability tensor is computed there, and
    ``engine.reuse.warn_schedule_conflicts`` is the one place that says so.
    The two causes are held apart: one cache with and without the
    controller, one controller over two caches. A cache that does not fit
    the plan is refused."""
    from p2p_tpu.models.unet import apply_unet, init_attn_cache

    layout = unet_layout(TINY.unet)
    plan = tuple("use" if m.is_cross else "off" for m in layout.metas)
    # The CFG-doubled batch: the controller edits the conditional half.
    two_b = 2 * len(PROMPTS)
    zeros = init_attn_cache(layout, two_b)
    ones = tuple(jnp.ones_like(c) for c in zeros)
    x = jnp.zeros((two_b,) + tiny_pipe.latent_shape)
    ctx = jnp.concatenate([encode_prompts(tiny_pipe, [""] * len(PROMPTS)),
                           encode_prompts(tiny_pipe, PROMPTS)], axis=0)
    # Cross edit only (no self window), so every site it acts on is `use`.
    ctrl = layout.resolve(factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=1.0, self_replace_steps=0.0,
        tokenizer=tiny_pipe.tokenizer, max_len=TINY.text.max_length,
        store=False))

    def run(cache, ctrl=None, plan=plan):
        return apply_unet(tiny_pipe.unet_params, TINY.unet, x, jnp.int32(0),
                          ctx, layout=layout, controller=ctrl,
                          attn_cache=cache, site_plan=plan)

    # Not vacuous: where the cross sites compute, this controller moves eps.
    assert not np.array_equal(np.asarray(run(None, plan=None)[0]),
                              np.asarray(run(None, ctrl, plan=None)[0]))
    eps_zeros, _, out_zeros = run(zeros)
    eps_zeros_ctrl, _, out_zeros_ctrl = run(zeros, ctrl)
    eps_ones_ctrl, _, out_ones_ctrl = run(ones, ctrl)
    assert all(a is b for a, b in zip(out_zeros, zeros))
    assert all(a is b for a, b in zip(out_zeros_ctrl, zeros))
    assert all(a is b for a, b in zip(out_ones_ctrl, ones))
    # Same cache, with and without the controller: the edit is dropped.
    assert np.array_equal(np.asarray(eps_zeros), np.asarray(eps_zeros_ctrl))
    # Same controller, another cache: the cache is what the site returns.
    assert not np.array_equal(np.asarray(eps_zeros_ctrl),
                              np.asarray(eps_ones_ctrl))
    with pytest.raises(ValueError, match="attn_cache"):
        run(None)
    with pytest.raises(ValueError, match="attn_cache"):
        run(zeros, plan=None)


# ---------------------------------------------------------------------------
# Validation: gate × null-text, gate range
# ---------------------------------------------------------------------------


def test_gate_rejected_under_nulltext_embeddings(tiny_pipe):
    ups = jnp.zeros((STEPS, 1, TINY.text.max_length, TINY.unet.context_dim))
    with pytest.raises(ValueError, match="null-text"):
        text2image(tiny_pipe, PROMPTS[:1], None, num_steps=STEPS,
                   uncond_embeddings=ups, gate=GATE)
    # gate=T (feature off) stays allowed — the window is untouched.
    img, _, _ = text2image(tiny_pipe, PROMPTS[:1], None, num_steps=STEPS,
                           uncond_embeddings=ups, gate=STEPS)
    assert img.shape[0] == 1


def test_gate_rejected_in_invert(tiny_pipe):
    from p2p_tpu.engine.inversion import invert

    image = np.zeros((TINY.image_size, TINY.image_size, 3), np.uint8)
    with pytest.raises(ValueError, match="null-text"):
        invert(tiny_pipe, image, PROMPTS[0], num_steps=STEPS, gate=GATE)


def test_gate_rejected_in_nulltext_sweep(tiny_pipe):
    ctx, lats, ctrls = _sweep_inputs(tiny_pipe)
    ups = jnp.zeros((1, STEPS, 1, TINY.text.max_length,
                     TINY.unet.context_dim))
    with pytest.raises(ValueError, match="null-text"):
        sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS,
              uncond_per_step=ups, gate=GATE)


def test_resolve_gate_validation():
    assert resolve_gate(None, 10) == 10
    assert resolve_gate(0.5, 10) == 5
    assert resolve_gate(7, 10) == 7
    assert resolve_gate("auto", 10, None) == 5
    for bad in (0, 11, 0.0, 1.5, "half"):
        with pytest.raises(ValueError):
            resolve_gate(bad, 10)
