"""On-chip A/B experiments for the SD14 step-time budget.

Default run (round-3 set): baseline scan, gather-vs-broadcast upsample,
flash head-dim pad probe, batch scaling, VAE decode dtype. The round-2
small-site attention lowerings (dot_product_attention everywhere, flash down
to S>=1024) were measured and rejected (+46% step time; PERF.md) — rerun them
with --all.
"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from p2p_tpu.models import SD14, TINY, init_unet, unet_layout
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models import nn as nn_mod
from p2p_tpu.models.unet import apply_unet
from p2p_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

# P2P_EXP_PRESET=tiny: CPU smoke lane for the experiments themselves (the
# monkeypatched variants must run and stay exact before burning chip time).
cfg = TINY if os.environ.get("P2P_EXP_PRESET") == "tiny" else SD14
if cfg is SD14:
    from _bench_common import require_accelerator
    require_accelerator()
layout = unet_layout(cfg.unet)
params = init_unet(jax.random.PRNGKey(0), cfg.unet)
s = cfg.latent_size

def time_scan(B, label, steps=50):
    x = jnp.ones((B, s, s, cfg.unet.in_channels), jnp.bfloat16)
    ctx = jnp.ones((B, cfg.unet.context_len, cfg.unet.context_dim), jnp.bfloat16)
    @jax.jit
    def scan(params, x, ctx):
        def body(h, t):
            eps, _ = apply_unet(params, cfg.unet, h, t, ctx, layout=layout)
            return eps, None
        out, _ = jax.lax.scan(body, x, jnp.arange(steps, dtype=jnp.int32))
        return out
    t0 = time.perf_counter(); np.asarray(scan(params, x, ctx))
    compile_s = time.perf_counter() - t0
    best = 1e9
    for _ in range(2):
        t0 = time.perf_counter(); np.asarray(scan(params, x, ctx))
        best = min(best, time.perf_counter() - t0)
    print(f"{label:28s} B={B:2d}: {best/steps*1000:7.2f} ms/step  "
          f"({B/2 * steps / best / steps:5.2f} img/s-equiv x50step) compile {compile_s:.0f}s",
          flush=True)
    return best / steps

orig_fused = nn_mod.fused_attention
import p2p_tpu.models.unet as unet_mod

# --qkv: re-measure just baseline + the qkv-fused projection A/B (used when
# a window died before 5c, or after a fix to the experiment itself).
qkv_only = "--qkv" in sys.argv

# 1. baseline (current code: broadcast+reshape upsample, einsum f32 probs for
# S<2048, flash for 4096). Same program as _bench_common → warm-cache load.
t_base = time_scan(4, "baseline")

if not qkv_only:
    # 2. old gather-based upsample (pre-round-3) vs the landed
    # broadcast+reshape — quantifies the relayout win on-chip.
    orig_up = nn_mod.upsample_nearest_2x
    def upsample_resize(x):
        b, h, w, c = x.shape
        return jax.image.resize(x, (b, h * 2, w * 2, c), method="nearest")
    nn_mod.upsample_nearest_2x = upsample_resize
    unet_mod.nn.upsample_nearest_2x = upsample_resize
    time_scan(4, "upsample via image.resize")
    nn_mod.upsample_nearest_2x = orig_up
    unet_mod.nn.upsample_nearest_2x = orig_up

    # 3. head_dim pad 40→64 at the flash sites (MXU lane-efficiency probe;
    # semantically exact: zero-padded q/k leave logits unchanged, padded v
    # dims are sliced off). Theory says XLA/Mosaic pad internally and this
    # is a wash — measure to confirm.
    def fused_pad64(q, k, v, scale, mask=None):
        d = q.shape[-1]
        if mask is None and q.shape[-2] == k.shape[-2] and q.shape[-2] >= 2048 and d < 64:
            pad = [(0, 0)] * (q.ndim - 1) + [(0, 64 - d)]
            out = orig_fused(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                             scale)
            return out[..., :d]
        return orig_fused(q, k, v, scale, mask)
    nn_mod.fused_attention = fused_pad64
    unet_mod.nn.fused_attention = fused_pad64
    time_scan(4, "flash head_dim pad64")
    nn_mod.fused_attention = orig_fused
    unet_mod.nn.fused_attention = orig_fused

    # 4. batch scaling (the bench g-sweep's underlying scan cost).
    for B in (8, 16):
        time_scan(B, "baseline batchscale", steps=25)

    # 5. VAE decode bf16 vs f32
    vparams = vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae)
    for dt, name in ((jnp.float32, "vae f32"), (jnp.bfloat16, "vae bf16")):
        lat = jnp.ones((2, s, s, cfg.unet.in_channels), dt)
        vdec = jax.jit(lambda p, l: vae_mod.to_uint8(vae_mod.decode(p, cfg.vae, l)))
        np.asarray(vdec(vparams, lat))
        t0 = time.perf_counter(); np.asarray(vdec(vparams, lat))
        print(f"{name}: {(time.perf_counter()-t0)*1000:.0f} ms", flush=True)

    # 5b. head_dim pad 40->128 (full MXU lane width; same exactness argument
    # as pad64 -- measure whether Mosaic's internal padding already covers it).
    def fused_pad128(q, k, v, scale, mask=None):
        d = q.shape[-1]
        if mask is None and q.shape[-2] == k.shape[-2] and q.shape[-2] >= 2048 and d < 128:
            pad = [(0, 0)] * (q.ndim - 1) + [(0, 128 - d)]
            out = orig_fused(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                             scale)
            return out[..., :d]
        return orig_fused(q, k, v, scale, mask)
    nn_mod.fused_attention = fused_pad128
    unet_mod.nn.fused_attention = fused_pad128
    time_scan(4, "flash head_dim pad128")
    nn_mod.fused_attention = orig_fused
    unet_mod.nn.fused_attention = orig_fused

# 5c. QKV-fused projections: concat the q/k/v kernels inside the forward --
# one (P,C)x(C,3C) MXU op per self site (k/v fused at cross sites) instead
# of three separate dots; the concat is loop-invariant so XLA hoists it out
# of the scan. Exact parity (same weights, split after); identity
# controller only (bit-exact on CPU at TINY scale: same dots, split after).
orig_attn = unet_mod._apply_attention
def attn_fused_qkv(p, ln, x, context, heads, ctx, is_cross):
    meta = ctx.next_meta()
    assert meta.is_cross == is_cross
    assert not unet_mod.controller_touches(ctx.controller, meta), \
        "experiment assumes identity controller"
    b, pix, _ = x.shape
    residual, x = x, nn_mod.layer_norm(ln, x)
    if is_cross:
        q = nn_mod.linear(p["to_q"], x)
        kv = context @ jnp.concatenate(
            [p["to_k"]["kernel"], p["to_v"]["kernel"]], axis=1
        ).astype(context.dtype)
        k, v = jnp.split(kv, 2, axis=-1)
    else:
        qkv = x @ jnp.concatenate(
            [p["to_q"]["kernel"], p["to_k"]["kernel"], p["to_v"]["kernel"]],
            axis=1).astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    d_head = q.shape[-1] // heads
    scale = d_head ** -0.5
    def split_heads(t):
        return t.reshape(b, t.shape[1], heads, d_head).transpose(0, 2, 1, 3)
    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    out = nn_mod.fused_attention(q, k, v, scale)
    out = out.transpose(0, 2, 1, 3).reshape(b, pix, heads * d_head)
    return residual + nn_mod.linear(p["to_out"], out)
def _one_forward():
    x = jnp.ones((2, s, s, cfg.unet.in_channels), jnp.bfloat16)
    ctx = jnp.ones((2, cfg.unet.context_len, cfg.unet.context_dim), jnp.bfloat16)
    eps, _ = jax.jit(lambda p, x, c: apply_unet(
        p, cfg.unet, x, jnp.int32(0), c, layout=layout))(params, x, ctx)
    return np.asarray(eps)

ref_eps = _one_forward()
unet_mod._apply_attention = attn_fused_qkv
fused_eps = _one_forward()
err = float(np.abs(ref_eps.astype(np.float32) - fused_eps.astype(np.float32)).max())
print(f"qkv-fused parity max|Δeps| = {err:.3e}", flush=True)
if cfg is TINY:
    # On CPU the fused projection is the same dots split after — today this
    # measures exactly 0.0, and the tolerance exists only so an XLA upgrade
    # that re-tiles the wider contraction can't fail the smoke lane
    # spuriously; 1e-6 is still ~100× below any real fusion bug. (On TPU
    # the wider contraction may tile differently, so the smoke lane is
    # where near-exactness is enforced; the chip run still prints its err.)
    assert err <= 1e-6, f"qkv-fused projection diverged: max|Δeps|={err}"
time_scan(4, "qkv-fused projections")
unet_mod._apply_attention = orig_attn

if "--all" not in sys.argv:
    sys.exit(0)

# --- round-2 record: small-site attention lowerings (rejected; PERF.md) ---

# 6. dot_product_attention for ALL untouched sites
def fused_dpa(q, k, v, scale, mask=None):
    if mask is None:
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale)
        return out.transpose(0, 2, 1, 3)
    return orig_fused(q, k, v, scale, mask)
nn_mod.fused_attention = fused_dpa
unet_mod.nn.fused_attention = fused_dpa
time_scan(4, "dot_product_attention all")

# 7. flash kernel down to S>=1024 (32² sites), dpa below
from jax.experimental.pallas.ops.tpu import flash_attention as _fa
def fused_flash1024(q, k, v, scale, mask=None):
    s_q, s_k = q.shape[-2], k.shape[-2]
    if mask is None and s_q == s_k and s_q >= 1024:
        blk = next((b for b in (1024, 512, 256) if s_q % b == 0), 0)
        if blk:
            sizes = _fa.BlockSizes(block_q=blk, block_k_major=blk, block_k=blk,
                block_b=1, block_q_major_dkv=blk, block_k_major_dkv=blk,
                block_q_dkv=blk, block_k_dkv=blk)
            return _fa.flash_attention(q, k, v, causal=False, sm_scale=scale,
                                       block_sizes=sizes)
    return fused_dpa(q, k, v, scale, mask)
nn_mod.fused_attention = fused_flash1024
unet_mod.nn.fused_attention = fused_flash1024
time_scan(4, "flash>=1024 + dpa")
nn_mod.fused_attention = orig_fused
unet_mod.nn.fused_attention = orig_fused
