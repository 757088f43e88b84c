"""Fused-edit Pallas kernel tests (`p2p_tpu/kernels/`, ISSUE 16).

Everything runs in pallas interpret mode on CPU — the *identical* kernel
program that lowers on TPU, executed by the interpreter. Three layers of
coverage:

1. **Static dispatch** — `KernelConfig` validation / `from_fuse_plan`,
   `kernel_edit_spec` extraction per (controller, site), and
   `site_variant` / `engine.reuse.lower_kernel_plan`: which of the four
   variants (use / flash / fused-edit / materialized) every site compiles
   to. All trace-time; no kernel runs.
2. **Site-level parity** — `fused_site_attention` vs the materialized
   reference (`edit_attention_reference`: `attention_probs` →
   `apply_attention_control` → einsum) on random q/k/v at the real TINY
   site geometries, per edit family (replace / refine / reweight cross,
   self-injection) and per step across the blend-schedule boundary. The
   kernel reproduces the reference row algebra in f32, so tolerances are
   at f32-reassociation level, not the documented 1e-2 golden budget.
3. **End-to-end** — `text2image(..., kernels=KernelConfig(interpret=True))`
   vs the kernel-free run: controller-free must be *bitwise* (dispatch is
   program-invisible without edits), edited runs within tight tolerance.
   The default-on `kernel_parity` quality-gate leg pins the same contract
   across all families; these keep the cheapest legs in tier-1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.align.words import get_equalizer
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.kernel_spec import (
    LANE,
    edit_operands,
    kernel_edit_spec,
    padded_key_len,
)
from p2p_tpu.engine import reuse as R
from p2p_tpu.engine.sampler import text2image
from p2p_tpu.kernels import (
    VARIANT_FLASH,
    VARIANT_FUSED,
    VARIANT_MATERIALIZED,
    VARIANT_USE,
    KernelConfig,
    site_variant,
)
from p2p_tpu.kernels.dispatch import site_name
from p2p_tpu.kernels.fused_edit import (
    edit_attention_reference,
    fused_site_attention,
)
from p2p_tpu.models import TINY
from p2p_tpu.models.config import unet_layout
from tests.test_golden import _pipe

PROMPTS = ["a cat riding a bike", "the dog eating some pizza"]
STEPS = 3


@pytest.fixture(scope="module")
def pipe():
    return _pipe(TINY)


@pytest.fixture(scope="module")
def layout():
    return unet_layout(TINY.unet)


def _ctrl(pipe, mode="replace", store=False, self_max_pixels=None,
          prompts=None):
    prompts = list(prompts or PROMPTS)
    size = pipe.config.unet.sample_size
    kw = dict(tokenizer=pipe.tokenizer,
              max_len=pipe.config.text.max_length,
              self_max_pixels=(size * size if self_max_pixels is None
                               else self_max_pixels),
              store=store)
    if mode == "replace":
        return factory.attention_replace(prompts, STEPS, 0.8, 0.4, **kw)
    if mode == "refine":
        return factory.attention_refine(prompts, STEPS, 0.8, 0.4, **kw)
    assert mode == "reweight"
    eq = get_equalizer(prompts[0], [prompts[0].split()[1]], [3.0],
                       pipe.tokenizer, mode="paired")
    return factory.attention_reweight(prompts, STEPS, 0.8, 0.4, eq, **kw)


def _meta(layout, *, cross, pixels=None, stored=None):
    for m in layout.metas:
        if m.is_cross != cross:
            continue
        if pixels is not None and m.pixels != pixels:
            continue
        if stored is not None and (m.store_slot is not None) != stored:
            continue
        return m
    raise AssertionError(
        f"no TINY site with cross={cross} pixels={pixels} stored={stored}")


def _site_qkv(meta, seed=0, batch=4):
    d = meta.channels // meta.heads
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(batch, meta.heads, meta.pixels, d),
                    jnp.float32)
    k = jnp.asarray(rng.randn(batch, meta.heads, meta.key_len, d),
                    jnp.float32)
    v = jnp.asarray(rng.randn(batch, meta.heads, meta.key_len, d),
                    jnp.float32)
    return q, k, v, d ** -0.5


# ---------------------------------------------------------------- dispatch

def test_kernel_config_covers_and_validation():
    assert KernelConfig().covers("cross_attn/down0")
    cfg = KernelConfig(sites=("cross_attn/down0", "self_attn/mid1"))
    assert cfg.covers("self_attn/mid1")
    assert not cfg.covers("cross_attn/up1")
    with pytest.raises(ValueError, match="tuple"):
        KernelConfig(sites=["cross_attn/down0"])
    # Hashable — the whole point: it rides jit static arguments.
    assert hash(KernelConfig()) == hash(KernelConfig())


def test_kernel_config_from_fuse_plan():
    plan = {"fuse_order": [{"site": "self_attn/down0"},
                           {"site": "cross_attn/down0"},
                           {"site": "cross_attn/mid1"}]}
    cfg = KernelConfig.from_fuse_plan(plan)
    assert cfg.sites == ("self_attn/down0", "cross_attn/down0",
                         "cross_attn/mid1")
    top1 = KernelConfig.from_fuse_plan(plan, take=1, interpret=True)
    assert top1.sites == ("self_attn/down0",) and top1.interpret


def test_kernel_edit_spec_extraction(pipe, layout):
    ctrl = _ctrl(pipe)
    cross = _meta(layout, cross=True)
    spec = kernel_edit_spec(ctrl, cross)
    assert spec.kind == "replace" and spec.is_cross
    assert not spec.has_equalizer
    assert spec.key_len == pipe.config.text.max_length
    assert spec.pad_len == padded_key_len(spec.key_len) == LANE

    selfm = _meta(layout, cross=False)
    sspec = kernel_edit_spec(ctrl, selfm)
    assert sspec.kind == "none" and not sspec.is_cross
    assert sspec.key_len == selfm.pixels

    # Reweight carries the equalizer; refine carries the gather transform.
    assert kernel_edit_spec(_ctrl(pipe, "reweight"), cross).has_equalizer
    assert kernel_edit_spec(_ctrl(pipe, "refine"), cross).kind == "refine"

    # Not compilable: no controller; self site beyond the injection window;
    # a stored site under a store-carrying controller (the maps feed the
    # attention store — the materialization the kernel exists to avoid).
    assert kernel_edit_spec(None, cross) is None
    big_self = _meta(layout, cross=False,
                     pixels=max(m.pixels for m in layout.metas))
    narrow = _ctrl(pipe, self_max_pixels=big_self.pixels // 4)
    assert kernel_edit_spec(narrow, big_self) is None
    storer = _ctrl(pipe, store=True)
    stored = _meta(layout, cross=True, stored=True)
    free = _meta(layout, cross=True, stored=False)
    assert kernel_edit_spec(storer, stored) is None
    assert kernel_edit_spec(storer, free) is not None


def test_site_variant_vocabulary(pipe, layout):
    ctrl = _ctrl(pipe)
    cross = _meta(layout, cross=True)
    kc = KernelConfig(interpret=True)
    # Reuse 'use' segments serve the cache — no attention math at all.
    assert site_variant(kc, ctrl, cross, "use") == VARIANT_USE
    # Untouched sites take the library flash kernel, config or not.
    assert site_variant(kc, None, cross, "off") == VARIANT_FLASH
    assert site_variant(None, None, cross, "off") == VARIANT_FLASH
    # Touched + covered + compilable → the fused-edit kernel.
    assert site_variant(kc, ctrl, cross, "off") == VARIANT_FUSED
    # No config, or a config that does not cover the site → materialized.
    assert site_variant(None, ctrl, cross, "off") == VARIANT_MATERIALIZED
    other = KernelConfig(sites=("self_attn/mid1",))
    assert site_variant(other, ctrl, cross, "off") == VARIANT_MATERIALIZED
    # Stored site under a storing controller: touched but not compilable.
    storer = _ctrl(pipe, store=True)
    stored = _meta(layout, cross=True, stored=True)
    assert site_variant(kc, storer, stored, "off") == VARIANT_MATERIALIZED


def test_lower_kernel_plan_static_lowering(pipe, layout):
    n_cross = sum(1 for m in layout.metas if m.is_cross)
    n_self = len(layout.metas) - n_cross
    sched = R.ReuseSchedule(steps=4, cfg_gate=2,
                            cross=(2,) * n_cross, selfa=(4,) * n_self)
    ctrl = _ctrl(pipe)
    kc = KernelConfig(interpret=True)
    plan = R.lower_kernel_plan(layout, sched, ctrl, kc, phase=2)
    assert plan, "phase 2 produced no segments"
    seen = set()
    for seg, variants in plan:
        assert len(variants) == len(layout.metas)
        for m, mode, var in zip(layout.metas, seg.plan, variants):
            seen.add(var)
            if mode == "use":
                assert var == VARIANT_USE
            elif m.is_cross:
                # Phase 2 of this schedule serves every cross site from
                # cache; any non-use cross segment still lowers fused.
                assert var == VARIANT_FUSED
    assert VARIANT_USE in seen
    # kernels=None never lowers fused anywhere.
    for _, variants in R.lower_kernel_plan(layout, sched, ctrl, None,
                                           phase=1):
        assert VARIANT_FUSED not in variants


# ---------------------------------------------------------- site parity

@pytest.mark.parametrize("mode", ["replace", "refine", "reweight"])
@pytest.mark.parametrize("step", [0, 2])
def test_cross_site_parity(pipe, layout, mode, step):
    ctrl = _ctrl(pipe, mode)
    meta = _meta(layout, cross=True, pixels=256)
    q, k, v, scale = _site_qkv(meta, seed=hash(mode) % 1000)
    out = fused_site_attention(q, k, v, scale, ctrl, meta,
                               jnp.int32(step), interpret=True)
    assert out is not None, "site unexpectedly not kernel-compilable"
    ref = edit_attention_reference(q, k, v, scale, ctrl, meta,
                                   jnp.int32(step))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("step", [0, 1, 2])
def test_self_site_parity_across_injection_window(pipe, layout, step):
    # self_replace_steps=0.4 of 3 steps → injection ends at step 2: the
    # blend α flips from 1 to 0 inside the parametrized range, covering
    # both the inject-base-row and plain-softmax branches.
    ctrl = _ctrl(pipe)
    meta = _meta(layout, cross=False, pixels=64)
    q, k, v, scale = _site_qkv(meta, seed=step)
    out = fused_site_attention(q, k, v, scale, ctrl, meta,
                               jnp.int32(step), interpret=True)
    assert out is not None
    ref = edit_attention_reference(q, k, v, scale, ctrl, meta,
                                   jnp.int32(step))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_uncond_rows_are_plain_softmax(pipe, layout):
    # The uncond half and the base row never carry an edit — the kernel
    # computes the edit algebra and discards it there, so those rows must
    # match plain softmax attention with no controller in sight.
    from p2p_tpu.models import nn

    ctrl = _ctrl(pipe)
    meta = _meta(layout, cross=True, pixels=256)
    q, k, v, scale = _site_qkv(meta, seed=3)
    out = fused_site_attention(q, k, v, scale, ctrl, meta,
                               jnp.int32(0), interpret=True)
    probs = nn.attention_probs(q, k, scale)
    plain = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    b_half = q.shape[0] // 2
    np.testing.assert_allclose(np.asarray(out)[:b_half + 1],
                               np.asarray(plain)[:b_half + 1],
                               atol=1e-5, rtol=1e-5)


def test_fused_site_attention_fallbacks(pipe, layout):
    ctrl = _ctrl(pipe)
    meta = _meta(layout, cross=True, pixels=256)
    q, k, v, scale = _site_qkv(meta)
    step = jnp.int32(0)
    # No controller → no spec → None (caller keeps the reference path).
    assert fused_site_attention(q, k, v, scale, None, meta, step,
                                interpret=True) is None
    # No edit rows in the cond half (B=1): only trace-time shapes reveal
    # this, and the kernel needs base + ≥1 edit row.
    q1, k1, v1 = q[:2], k[:2], v[:2]
    assert fused_site_attention(q1, k1, v1, scale, ctrl, meta, step,
                                interpret=True) is None
    # A block_q that does not tile the pixel axis → None, not a crash.
    assert fused_site_attention(q, k, v, scale, ctrl, meta, step,
                                block_q=3, interpret=True) is None


def test_edit_operands_padding(pipe, layout):
    # Padded key columns must be inert: zero transform rows, α = 0,
    # equalizer 1 — so they contribute nothing even multiplied in.
    ctrl = _ctrl(pipe, "reweight")
    meta = _meta(layout, cross=True)
    spec = kernel_edit_spec(ctrl, meta)
    ops = edit_operands(ctrl.edit, spec, jnp.int32(0))
    k, kp = spec.key_len, spec.pad_len
    assert ops["blend"].shape[-1] == kp
    assert np.all(np.asarray(ops["blend"])[:, k:] == 0.0)
    assert np.all(np.asarray(ops["equalizer"])[:, k:] == 1.0)


# ------------------------------------------------------------ end-to-end

def test_e2e_no_controller_bitwise(pipe):
    rng = jax.random.PRNGKey(7)
    img_a, xt_a, _ = text2image(pipe, PROMPTS, None, num_steps=STEPS,
                                rng=rng)
    img_b, xt_b, _ = text2image(pipe, PROMPTS, None, num_steps=STEPS,
                                rng=rng, kernels=KernelConfig(interpret=True))
    np.testing.assert_array_equal(np.asarray(img_a), np.asarray(img_b))
    np.testing.assert_array_equal(np.asarray(xt_a), np.asarray(xt_b))


def test_e2e_replace_fused_matches_reference(pipe):
    ctrl = _ctrl(pipe)
    rng = jax.random.PRNGKey(7)
    img_r, xt_r, _ = text2image(pipe, PROMPTS, ctrl, num_steps=STEPS,
                                rng=rng)
    img_f, xt_f, _ = text2image(pipe, PROMPTS, ctrl, num_steps=STEPS,
                                rng=rng, kernels=KernelConfig(interpret=True))
    np.testing.assert_allclose(np.asarray(xt_f, np.float64),
                               np.asarray(xt_r, np.float64), atol=1e-5)
    d = np.abs(np.asarray(img_f).astype(np.int16)
               - np.asarray(img_r).astype(np.int16))
    assert d.max() <= 1, f"image max|Δ|={d.max()}"
