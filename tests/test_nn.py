"""Numerics of the nn toolkit's bf16 fast paths.

The bf16 norm paths keep full-tensor traffic in bf16 (profiling showed the
old f32-materializing path cost ~8% of SD-1.4 step time in conv-output write
bandwidth); these tests pin their error against an exact-f32 oracle applied
to the SAME bf16-quantized input — i.e. they bound the *algorithm's* error,
excluding inherent input quantization."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.models import nn


def _gn_oracle(x_f32, groups, eps=1e-5):
    s = x_f32.shape
    xg = x_f32.reshape(s[:-1] + (groups, s[-1] // groups))
    red = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    m = xg.mean(axis=red, keepdims=True)
    v = xg.var(axis=red, keepdims=True)
    return ((xg - m) / np.sqrt(v + eps)).reshape(s)


@pytest.mark.parametrize("mean,std", [(0, 1), (20, 1), (100, 0.1),
                                      (500, 0.5), (100, 10), (-50, 2)])
def test_group_norm_bf16_matches_f32_oracle_on_same_input(mean, std):
    rng = np.random.RandomState(0)
    shape, groups = (2, 8, 8, 16), 4
    x = (rng.randn(*shape) * std + mean).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    p = {"scale": np.ones(16, np.float32), "bias": np.zeros(16, np.float32)}
    ref = _gn_oracle(np.asarray(xb, np.float32), groups)
    got = np.asarray(nn.group_norm(p, xb, groups)).astype(np.float32)
    # bf16 arithmetic noise only — must NOT scale with |mean|/std (the
    # failure mode of naive y = x·inv + shift factoring).
    assert np.abs(got - ref).max() < 0.1


def test_group_norm_bf16_constant_input_is_bias():
    x = jnp.full((1, 4, 4, 8), 13.3, jnp.bfloat16)
    p = {"scale": np.ones(8, np.float32), "bias": np.full(8, 0.25, np.float32)}
    out = np.asarray(nn.group_norm(p, x, 4)).astype(np.float32)
    np.testing.assert_allclose(out, 0.25, atol=1e-2)


def test_group_norm_f32_path_unchanged():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 6, 8).astype(np.float32) * 3 + 7
    p = {"scale": rng.randn(8).astype(np.float32),
         "bias": rng.randn(8).astype(np.float32)}
    got = np.asarray(nn.group_norm(p, jnp.asarray(x), 4))
    want = _gn_oracle(x, 4) * p["scale"] + p["bias"]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("mean,std", [(0, 1), (100, 0.1), (500, 0.5)])
def test_layer_norm_bf16_matches_f32_oracle_on_same_input(mean, std):
    rng = np.random.RandomState(2)
    x = (rng.randn(2, 9, 32) * std + mean).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    p = {"scale": np.ones(32, np.float32), "bias": np.zeros(32, np.float32)}
    xf = np.asarray(xb, np.float32)
    m = xf.mean(-1, keepdims=True)
    v = xf.var(-1, keepdims=True)
    ref = (xf - m) / np.sqrt(v + 1e-5)
    got = np.asarray(nn.layer_norm(p, xb)).astype(np.float32)
    assert np.abs(got - ref).max() < 0.1


def test_upsample_nearest_2x_matches_jax_image_resize():
    rng = np.random.RandomState(3)
    for shape in ((2, 4, 4, 3), (1, 8, 16, 5), (3, 1, 1, 2)):
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        b, h, w, c = shape
        want = jax.image.resize(x, (b, h * 2, w * 2, c), method="nearest")
        got = nn.upsample_nearest_2x(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_attention_large_site_matches_reference_on_cpu():
    # Off the TPU a site the flash kernel would take keeps the einsum chain
    # (tests/test_flash_pallas.py runs the kernel itself, interpreted).
    rng = np.random.RandomState(4)
    s, d = 2048, 16
    mk = lambda: jnp.asarray(rng.randn(1, 2, s, d).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    scale = d ** -0.5
    got = nn.fused_attention(q, k, v, scale)
    probs = nn.attention_probs(q, k, scale).astype(v.dtype)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_fused_attention_mask_uses_einsum_path():
    rng = np.random.RandomState(5)
    s, d = 64, 8
    mk = lambda: jnp.asarray(rng.randn(1, 1, s, d).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    mask = jnp.where(jnp.arange(s)[None, None, None, :] > s // 2, -1e9, 0.0)
    got = nn.fused_attention(q, k, v, d ** -0.5, mask)
    probs = nn.attention_probs(q, k, d ** -0.5, mask).astype(v.dtype)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
