"""Minimal functional NN toolkit: explicit param pytrees + pure apply fns.

Why not flax.linen: the prompt-to-prompt hook must thread controller store
state through every attention call site *in call order* and return it from the
model forward. With explicit (params, x, state) -> (y, state) functions that
threading is plain dataflow, the param tree maps 1:1 onto checkpoint names,
and everything is trivially jit/pjit/scan-compatible. All spatial tensors are
NHWC (TPU-native layout); compute dtype is a caller choice (bf16 on TPU),
while normalization statistics and softmax run in f32.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

Params = Dict[str, Any]


def _split(key, n):
    return jax.random.split(key, n)


# ---------------------------------------------------------------------------
# Linear / Conv
# ---------------------------------------------------------------------------


def linear_init(key, in_dim: int, out_dim: int, bias: bool = True,
                dtype=jnp.float32) -> Params:
    kk, _ = _split(key, 2)
    scale = 1.0 / math.sqrt(in_dim)
    p = {"kernel": jax.random.uniform(kk, (in_dim, out_dim), dtype, -scale, scale)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def linear_1x1(p: Params, x: jax.Array) -> jax.Array:
    """Apply a 1×1-conv parameter (HWIO kernel (1,1,I,O)) as a linear over a
    token-major (B, P, C) tensor — same math, no spatial relayout."""
    q = {"kernel": p["kernel"][0, 0]}
    if "bias" in p:
        q["bias"] = p["bias"]
    return linear(q, x)


def conv_init(key, in_ch: int, out_ch: int, kernel: int = 3, bias: bool = True,
              dtype=jnp.float32) -> Params:
    kk, _ = _split(key, 2)
    fan_in = in_ch * kernel * kernel
    scale = 1.0 / math.sqrt(fan_in)
    p = {"kernel": jax.random.uniform(kk, (kernel, kernel, in_ch, out_ch), dtype,
                                      -scale, scale)}
    if bias:
        p["bias"] = jnp.zeros((out_ch,), dtype)
    return p


def conv2d(p: Params, x: jax.Array, stride: int = 1, padding: str | int = "SAME"
           ) -> jax.Array:
    """NHWC conv; weight layout HWIO."""
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    y = jax.lax.conv_general_dilated(
        x, p["kernel"].astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms (statistics in f32 regardless of compute dtype)
# ---------------------------------------------------------------------------


def norm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def group_norm(p: Params, x: jax.Array, groups: int = 32, eps: float = 1e-5
               ) -> jax.Array:
    """GroupNorm over an NHWC (or N...C) tensor.

    Statistics accumulate in f32 regardless of carrier dtype; the
    normalization arithmetic stays in the carrier dtype. On the bf16 TPU path
    this keeps the producing conv's output bf16 — profiling showed XLA
    otherwise folds an x.astype(f32) into the conv fusion and writes f32,
    doubling HBM write traffic on every GN-feeding conv (~8% of step time at
    SD-1.4 shapes). f32 inputs are unaffected (stats math is then pure f32).
    """
    if x.dtype == jnp.float32:
        # Full-precision path (CPU tests / parity harness): all math in f32.
        c = x.shape[-1]
        g = min(groups, c)
        xg = x.reshape(x.shape[:-1] + (g, c // g))
        red = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
        mean = xg.mean(axis=red, keepdims=True)
        var = xg.var(axis=red, keepdims=True)
        xg = (xg - mean) * jax.lax.rsqrt(var + eps)
        return xg.reshape(x.shape) * p["scale"] + p["bias"]

    c = x.shape[-1]
    g = min(groups, c)
    xg = x.reshape(x.shape[:-1] + (g, c // g))
    red = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    # Shifted two-pass statistics, all full-tensor traffic in the carrier
    # dtype: center by the bf16-rounded mean (the subtraction x − m16 is
    # Sterbenz-exact for values near the mean, so no |mean|/std-scaled error),
    # accumulate the centered second moment in f32, and fold the f32 rounding
    # residual (mean − m16) into the per-group shift. XLA input-fuses the
    # f32-accumulating reductions — the bf16 tensor is never materialized
    # as f32 in HBM (that materialization was ~8% of SD-1.4 step time).
    mean = jnp.mean(xg, axis=red, keepdims=True, dtype=jnp.float32)
    m16 = mean.astype(x.dtype)
    centered = xg - m16
    cvar = jnp.mean(jnp.square(centered.astype(jnp.float32)), axis=red,
                    keepdims=True)
    resid = mean - m16.astype(jnp.float32)
    var = cvar - jnp.square(resid)
    expand = (None,) * (xg.ndim - 2)
    inv = (jax.lax.rsqrt(var + eps)
           * p["scale"].astype(jnp.float32).reshape((g, c // g))[expand])
    shift = (p["bias"].astype(jnp.float32).reshape((g, c // g))[expand]
             - resid * inv)
    y = centered * inv.astype(x.dtype) + shift.astype(x.dtype)
    return y.reshape(x.shape)


def layer_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """LayerNorm; f32 statistics, carrier-dtype tensor arithmetic (see
    group_norm for why and for the shifted-two-pass precision argument)."""
    if x.dtype == jnp.float32:
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        return y * p["scale"] + p["bias"]
    mean = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    m16 = mean.astype(x.dtype)
    centered = x - m16
    cvar = jnp.mean(jnp.square(centered.astype(jnp.float32)), axis=-1,
                    keepdims=True)
    resid = mean - m16.astype(jnp.float32)
    var = cvar - jnp.square(resid)
    inv = jax.lax.rsqrt(var + eps)
    scale_shift = (p["bias"].astype(jnp.float32)
                   - resid * inv * p["scale"].astype(jnp.float32))
    y = (centered * inv.astype(x.dtype)) * p["scale"].astype(x.dtype)
    return y + scale_shift.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / embeddings
# ---------------------------------------------------------------------------


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def quick_gelu(x):
    """CLIP's activation: x * sigmoid(1.702 x)."""
    return x * jax.nn.sigmoid(1.702 * x)


def upsample_nearest_2x(x: jax.Array) -> jax.Array:
    """Exact 2× nearest-neighbor upsample of an NHWC tensor.

    Bit-identical to ``jax.image.resize(..., method="nearest")`` at integer
    scale 2 (each output pixel reads input ``i // 2``), but expressed as
    broadcast+reshape so XLA lowers it to a tiled copy instead of the gather
    the general resize op can produce — this sits on the U-Net's per-step
    up path (3 levels × 50 steps) and the VAE decoder."""
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, 2, w, 2, c))
    return x.reshape(b, h * 2, w * 2, c)


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0,
                       dtype=jnp.float32) -> jax.Array:
    """Sinusoidal timestep embedding, diffusers `Timesteps` semantics
    (flip_sin_to_cos=True, downscale_freq_shift=0): [cos | sin] halves."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[..., None] * freqs
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, [(0, 0)] * (emb.ndim - 1) + [(0, 1)])
    return emb.astype(dtype)


# ---------------------------------------------------------------------------
# Attention core
# ---------------------------------------------------------------------------


def attention_probs(q: jax.Array, k: jax.Array, scale: float,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """Materialized softmax(QKᵀ·scale) in f32 — the tensor prompt-to-prompt
    edits (`/root/reference/ptp_utils.py:195-205`). q,k: (B, heads, S, D)."""
    sim = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                     preferred_element_type=jnp.float32) * scale
    if mask is not None:
        sim = sim + mask
    return jax.nn.softmax(sim.astype(jnp.float32), axis=-1)


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """Attention for call sites the controller provably never reads
    (`/root/reference/main.py:131,170` never touches 64²-pixel maps).

    q,k,v: (B, heads, S, D); mask: additive, broadcastable to
    (B, heads, Sq, Sk). Large self-attention (S ≥ 2048, e.g. the 64²-pixel
    sites) runs the Pallas TPU flash kernel when ``flash_block`` finds a
    VMEM-feasible block for the head geometry — blockwise, never
    materializing the (S, S) probability tensor; measured ~3× over XLA's
    attention at the SD-1.4 64² shape on v5e. Small maps use a plain einsum
    chain (kernel launch would cost more than it saves)."""
    s_q, s_k = q.shape[-2], k.shape[-2]
    if mask is None and s_q == s_k and s_q >= 2048:
        blk = flash_block(s_q, q.shape[-1], q.dtype.itemsize)
        if blk and _on_tpu():
            return flash_attention_tpu(q, k, v, scale, blk)
        # Non-TPU accelerators, or no VMEM-feasible block for this head
        # geometry: let XLA pick its attention lowering rather than
        # materializing the (S, S) probabilities explicitly.
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale)
        return out.transpose(0, 2, 1, 3)
    probs = attention_probs(q, k, scale, mask).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# Stay under the TPU's 16 MiB scoped-VMEM budget with headroom: the flash
# kernel's resident footprint per grid step is ~(q + k + v + double-buffered
# k/v) blocks in the input dtype plus f32 accumulator/statistics scratch,
# ≈ block·head_dim·(8·itemsize + 8) bytes (within ~5% of the 19 MiB the
# compiler reports for block 1024, D=512, f32 — the VAE mid-attention shape
# that OOMs scoped vmem if block size ignores head_dim).
_FLASH_VMEM_BUDGET = 14 * 2**20


def flash_block(seq_len: int, head_dim: int, itemsize: int) -> int:
    """Largest power-of-two block that tiles ``seq_len`` (the Pallas kernel
    requires seq_len % block == 0) AND keeps the kernel's scoped-VMEM
    footprint inside the TPU budget for this ``head_dim``/``itemsize``;
    0 → no viable block (einsum/XLA path instead). The geometry args are
    deliberately required: a default would make the VMEM guard opt-in, and
    a wide-head f32 call site (the VAE mid-attention shape) that omitted
    them would compile-time-OOM scoped VMEM on the chip."""
    for b in (1024, 512, 256):
        if seq_len % b == 0 and b * head_dim * (8 * itemsize + 8) <= _FLASH_VMEM_BUDGET:
            return b
    return 0


def edit_block(pixels: int, key_len: int, head_dim: int, itemsize: int) -> int:
    """Largest query block for the fused-edit kernel (``kernels.fused_edit``)
    that tiles ``pixels`` and stays inside the scoped-VMEM budget; 0 → no
    viable block (the site keeps the materialized reference path).

    The edit kernel's resident footprint per grid step differs from the
    flash kernel's (``flash_block``): the key axis is NOT blocked — a full
    lane-padded ``Kp`` lives in VMEM so edit rows see whole probability rows
    — and each instance holds its own + the base row's tiles. Per block:
    3 q/out tiles (own q, base q, out) + 3 key-axis tiles (k, base k, v) in
    the carrier dtype, 3 f32 probability tiles (own, base, edited), the
    ``(Kp, Kp)`` f32 edit transform, and f32 matmul accumulators. Same
    14 MiB budget (of the 16 MiB scoped VMEM) as the flash geometry —
    see the headroom note above ``_FLASH_VMEM_BUDGET``."""
    kp = max(128, -(-key_len // 128) * 128)

    def vmem(bq: int) -> int:
        return (3 * bq * head_dim * itemsize + 3 * kp * head_dim * itemsize
                + 3 * bq * kp * 4 + kp * kp * 4 + 2 * bq * head_dim * 4)

    for bq in (512, 256, 128):
        if pixels % bq == 0 and vmem(bq) <= _FLASH_VMEM_BUDGET:
            return bq
    # Small or non-power-of-two maps (edited self sites, tiny test configs):
    # one block over the whole query axis if it fits.
    if pixels < 128 or all(pixels % bq for bq in (512, 256, 128)):
        if vmem(pixels) <= _FLASH_VMEM_BUDGET:
            return pixels
    return 0


def _flash_block_sizes(blk: int):
    """The one BlockSizes geometry every flash call site uses — forward and
    residuals variants must stay on the same tiling.

    ALL backward blocks (dkv AND dq passes) must be specified or
    differentiating any program containing the kernel raises at trace time
    ("not all backward blocks are specified") — null-text inversion
    backprops through the U-Net's S=4096 flash sites, which is exactly how
    this surfaced on chip. The backward passes hold more live
    tiles than the forward, so they get a capped block; correctness of the
    spec is pinned by an interpret-mode grad test
    (tests/test_flash_pallas.py)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    bwd = min(blk, 512)
    return _fa.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=bwd, block_k_major_dkv=bwd,
        block_q_dkv=bwd, block_k_dkv=bwd,
        block_k_major_dq=bwd, block_k_dq=bwd, block_q_dq=bwd)


# The SPMD partitioner cannot partition a Mosaic kernel ("Mosaic kernels
# cannot be automatically partitioned. Please wrap the call in a shard_map" —
# how `serve --mesh dp=4` first failed on four chips). The dp sweep programs
# therefore trace their body under `kernel_mesh(mesh)`, and every Pallas call
# goes through `per_device`: a shard_map with nothing sharded, which the
# enclosing `vmap(..., spmd_axis_name="dp")` turns into one kernel instance
# per device over that device's own groups.
_KERNEL_MESH: Optional[Mesh] = None


@contextlib.contextmanager
def kernel_mesh(mesh: Optional[Mesh]):
    """Trace-time: Pallas kernels traced inside run per device of ``mesh``
    (None: no wrapping — single-device programs, and sp ring attention,
    which brings its own shard_map)."""
    global _KERNEL_MESH
    prev, _KERNEL_MESH = _KERNEL_MESH, mesh
    try:
        yield
    finally:
        _KERNEL_MESH = prev


def per_device(kernel):
    """``kernel`` (arrays in, arrays out) as it must be called inside a
    mesh-partitioned program; see :func:`kernel_mesh`."""
    if _KERNEL_MESH is None:
        return kernel
    return jax.shard_map(kernel, mesh=_KERNEL_MESH, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array,
                        scale: float, blk: int) -> jax.Array:
    """The Pallas TPU flash kernel call `fused_attention` takes at the big
    self-attention sites. Kept as a named function so the CPU suite can run
    the identical code under `pltpu.force_tpu_interpret_mode()`
    (tests/test_flash_pallas.py) — the kernel otherwise only executes on
    a TPU."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    def kernel(q, k, v):
        return _fa.flash_attention(q, k, v, causal=False, sm_scale=scale,
                                   block_sizes=_flash_block_sizes(blk))

    return per_device(kernel)(q, k, v)


def flash_attention_residuals(q: jax.Array, k: jax.Array, v: jax.Array,
                              scale: float, blk: int):
    """Flash kernel returning ``(out, l, m)`` — the normalized output plus
    per-row softmax statistics (sum ``l`` and max ``m`` of the local logits).
    These are the pieces ring attention needs to merge partial results across
    devices without ever materializing local (Sq, Sk) scores
    (`parallel/ring.py`). Semantics pinned by tests/test_flash_pallas.py in
    interpret mode."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    return _fa._flash_attention(q, k, v, None, None, True, False, scale,
                                _flash_block_sizes(blk), False)


def _on_tpu() -> bool:
    """Static platform gate: the Pallas flash kernel only lowers on TPU
    (tests run on the CPU backend and take the einsum path)."""
    return jax.default_backend() == "tpu"
