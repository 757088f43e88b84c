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


#: Channels per group of every GroupNorm the cells run: the VAE's 128 wide
#: (4), the U-Net's 320, 640, 960, 1280, 1920 and 2560 wide (10 ... 80).
CHANNELS_PER_GROUP = (4, 10, 20, 30, 40, 60, 80)


def _gn_oracle64(x, groups, scale, bias, eps=1e-5):
    """GroupNorm in float64 by the textbook's (N, ..., groups, C/groups) view."""
    return _gn_oracle(x.astype(np.float64), groups, eps) * scale + bias


def _gn_params(rng, c):
    return {"scale": rng.randn(c).astype(np.float32),
            "bias": rng.randn(c).astype(np.float32)}


@pytest.mark.parametrize("mean_in_std", [0, 100])
@pytest.mark.parametrize("per_group", CHANNELS_PER_GROUP)
def test_group_norm_f32_matches_a_float64_oracle(per_group, mean_in_std):
    rng = np.random.RandomState(per_group)
    groups, std = 32, 3.0
    c = groups * per_group
    # every channel its own offset inside the group too, so that the second
    # moment about the group's mean is not the channel's own
    x = (rng.randn(2, 6, 5, c) * std + rng.randn(c) * std
         + mean_in_std * std).astype(np.float32)
    p = _gn_params(rng, c)
    got = np.asarray(nn.group_norm(p, jnp.asarray(x), groups))
    want = _gn_oracle64(x, groups, p["scale"], p["bias"])
    # 1e-5 as test_group_norm_f32_path_unchanged; at a mean of 100 standard
    # deviations the input's own f32 rounding (6e-8 of 100) is what is left
    # of a normalized value, times the scale.
    tol = 1e-5 * max(1.0, 0.1 * mean_in_std)
    np.testing.assert_allclose(got, want, atol=tol * np.abs(p["scale"]).max(),
                               rtol=1e-5)


@pytest.mark.parametrize("per_group", CHANNELS_PER_GROUP)
def test_group_norm_f32_of_a_constant_is_the_bias(per_group):
    rng = np.random.RandomState(per_group)
    c = 32 * per_group
    p = _gn_params(rng, c)
    x = jnp.full((2, 4, 4, c), 13.3, jnp.float32)
    # a mean of sixteen 13.3s is 13.3 to a rounding or two, and what is left
    # of it meets 1 / sqrt(eps), not a standard deviation
    tol = 4 * np.spacing(np.float32(13.3)) / np.sqrt(1e-5)
    np.testing.assert_allclose(np.asarray(nn.group_norm(p, x, 32)),
                               np.broadcast_to(p["bias"], x.shape),
                               atol=tol * np.abs(p["scale"]).max())


@pytest.mark.parametrize("per_group", CHANNELS_PER_GROUP)
def test_group_norm_f32_grad_matches_the_oracles(per_group):
    # engine/inversion.py differentiates through the U-Net's norms.
    rng = np.random.RandomState(100 + per_group)
    groups, eps = 32, 1e-5
    c = groups * per_group
    x = (rng.randn(2, 4, 3, c) * 2 + rng.randn(c) + 5).astype(np.float32)
    w = rng.randn(*x.shape).astype(np.float32)
    p = _gn_params(rng, c)
    got = np.asarray(jax.grad(
        lambda x: (nn.group_norm(p, x, groups, eps) * w).sum())(jnp.asarray(x)))
    # d/dx of sum(w * (xhat * scale + bias)) in float64, group by group
    s = x.shape
    view = s[:-1] + (groups, per_group)
    red = tuple(range(1, len(view) - 2)) + (len(view) - 1,)
    xg = x.astype(np.float64).reshape(view)
    gy = (w.astype(np.float64) * p["scale"]).reshape(view)
    var = xg.var(axis=red, keepdims=True)
    xhat = (xg - xg.mean(axis=red, keepdims=True)) / np.sqrt(var + eps)
    want = ((gy - gy.mean(axis=red, keepdims=True)
             - xhat * (gy * xhat).mean(axis=red, keepdims=True))
            / np.sqrt(var + eps)).reshape(s)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_group", (4, 10, 40))
def test_group_norm_shift_is_the_shift_added(per_group, dtype):
    """``shift=t`` is GroupNorm of ``x + t[:, None, None, :]``, in the f32
    branch without the sum's ever being formed."""
    rng = np.random.RandomState(200 + per_group)
    c = 32 * per_group
    x = jnp.asarray(rng.randn(3, 5, 4, c) * 2 + 1, dtype)
    t = jnp.asarray(rng.randn(3, c) * 3, dtype)
    p = _gn_params(rng, c)
    got = nn.group_norm(p, x, 32, shift=t)
    want = nn.group_norm(p, x + t[:, None, None, :], 32)
    assert got.dtype == want.dtype
    if dtype == jnp.bfloat16:          # the same program: the sum is formed
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5 * np.abs(p["scale"]).max(),
                                   rtol=1e-5)
        want64 = _gn_oracle64(
            np.asarray(x) + np.asarray(t)[:, None, None, :].astype(np.float64),
            32, p["scale"], p["bias"])
        np.testing.assert_allclose(np.asarray(got), want64,
                                   atol=1e-5 * np.abs(p["scale"]).max(),
                                   rtol=1e-5)


@pytest.mark.parametrize("in_ch,out_ch", [(64, 64), (96, 64), (320, 640)])
def test_resnet_block_with_the_shift_folded_is_the_block(in_ch, out_ch):
    """``unet._apply_resnet`` hands the time-embedding shift to norm2; the
    block as it stood added it to conv1's output at full size."""
    from p2p_tpu.models import unet

    def as_it_stood(p, x, temb, groups):
        h = nn.conv2d(p["conv1"], nn.silu(nn.group_norm(p["norm1"], x, groups)))
        h = h + nn.linear(p["time_proj"], nn.silu(temb))[:, None, None, :]
        h = nn.conv2d(p["conv2"], nn.silu(nn.group_norm(p["norm2"], h, groups)))
        if "skip" in p:
            x = nn.conv2d(p["skip"], x)
        return x + h

    rng = np.random.RandomState(in_ch + out_ch)
    p = unet._resnet_init(jax.random.PRNGKey(in_ch), in_ch, out_ch, 48)
    for norm, ch in (("norm1", in_ch), ("norm2", out_ch)):
        p[norm] = {k: jnp.asarray(v) for k, v in _gn_params(rng, ch).items()}
    p["time_proj"]["bias"] = jnp.asarray(rng.randn(out_ch), jnp.float32)
    x = jnp.asarray(rng.randn(2, 8, 8, in_ch) * 2 + 1, jnp.float32)
    temb = jnp.asarray(rng.randn(2, 48) * 3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = unet._apply_resnet(p, x, temb, 32)
        want = as_it_stood(p, x, temb, 32)
    assert ("skip" in p) == (in_ch != out_ch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()),
                               rtol=1e-5)


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
