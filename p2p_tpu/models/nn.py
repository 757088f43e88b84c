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


def _kernel_init(key, shape, fan_in: int, kernel_dtype) -> jax.Array:
    """A kernel drawn in float32 and stored in ``kernel_dtype``: a kernel is
    only ever an operand of a product or convolution, which the MXU rounds
    to bfloat16 at the default precision whatever it is stored in."""
    scale = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale).astype(
        kernel_dtype)


def linear_init(key, in_dim: int, out_dim: int, bias: bool = True,
                kernel_dtype=jnp.float32) -> Params:
    kk, _ = _split(key, 2)
    p = {"kernel": _kernel_init(kk, (in_dim, out_dim), in_dim, kernel_dtype)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), jnp.float32)
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
              kernel_dtype=jnp.float32) -> Params:
    kk, _ = _split(key, 2)
    p = {"kernel": _kernel_init(kk, (kernel, kernel, in_ch, out_ch),
                                in_ch * kernel * kernel, kernel_dtype)}
    if bias:
        p["bias"] = jnp.zeros((out_ch,), jnp.float32)
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


def _over_space(v: jax.Array, x: jax.Array) -> jax.Array:
    """A per-(sample, channel) vector ``(N, C)`` against ``x`` ``(N, ..., C)``."""
    return v[(slice(None),) + (None,) * (x.ndim - 2)]


def _group_mean(v: jax.Array, groups: int) -> jax.Array:
    """``(N, C)``: every channel's entry replaced by the mean over the
    channels of its group."""
    n, c = v.shape
    m = v.reshape(n, groups, c // groups).mean(-1, keepdims=True)
    return jnp.broadcast_to(m, (n, groups, c // groups)).reshape(n, c)


def group_norm(p: Params, x: jax.Array, groups: int = 32, eps: float = 1e-5,
               shift: Optional[jax.Array] = None) -> jax.Array:
    """GroupNorm over an NHWC (or N...C) tensor.

    ``shift`` (``(N, C)``, optional) is a per-(sample, channel) vector the
    input carries without its having been added: the result is GroupNorm of
    ``x + shift[:, None, None, :]`` (a ResNet block's time-embedding shift
    feeds nothing but the block's second norm, so it is folded in here and
    never broadcast to the activation's size).

    Statistics accumulate in f32 regardless of carrier dtype; the
    normalization arithmetic stays in the carrier dtype. On the bf16 TPU path
    this keeps the producing conv's output bf16 — profiling showed XLA
    otherwise folds an x.astype(f32) into the conv fusion and writes f32,
    doubling HBM write traffic on every GN-feeding conv (~8% of step time at
    SD-1.4 shapes). f32 inputs are unaffected (stats math is then pure f32).
    """
    if x.dtype == jnp.float32:
        # All math in f32, and everything that is per (sample, channel) stays
        # an (N, C) vector in the activation's own channels-minor layout: both
        # moments are spatial reductions per channel, the channels of a group
        # are combined on the vector, and the tensor sees one centre and one
        # scale. ``var`` of a (N, H, W, groups, C/groups) view of the
        # activation made XLA:TPU transpose it whole to a W-minor layout first
        # (a group of 10 channels fills no lane tile: PERF.md §6, PR 32).
        # Two passes, the second moment about the group's mean: nothing
        # cancels, whatever the mean.
        g = min(groups, x.shape[-1])
        space = tuple(range(1, x.ndim - 1))
        offset = 0.0 if shift is None else shift
        centre = _group_mean(x.mean(axis=space) + offset, g) - offset
        xc = x - _over_space(centre, x)
        var = _group_mean(jnp.square(xc).mean(axis=space), g)
        inv = jax.lax.rsqrt(var + eps) * p["scale"]
        return xc * _over_space(inv, x) + p["bias"]

    if shift is not None:
        return group_norm(p, x + _over_space(shift, x), groups, eps)
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
    (B, heads, Sq, Sk). One of two implementations, chosen from the shapes
    alone: the library's Pallas TPU flash kernel (blockwise, the (S, S)
    probabilities never reach HBM) where ``flash_block`` has a geometry for
    the site, else the einsum chain over materialized probabilities. Gate
    and geometry are the measured ones of PERF.md §6 (PR 28: a sweep of
    both implementations on a v5e, head split and merge included)."""
    s_q = q.shape[-2]
    geometry = flash_block(s_q, q.shape[-1],
                           flash_operand_dtype(q.dtype).itemsize)
    if (mask is None and s_q == k.shape[-2] and geometry is not None
            and _on_tpu()):
        return flash_attention_tpu(q, k, v, scale, geometry)
    probs = attention_probs(q, k, scale, mask).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# The TPU gives a kernel 16 MiB of scoped VMEM; stay under it with headroom.
# 14.75 MiB is what ``_flash_vmem_bytes`` counts for (512, 4608, 1536) at 2 B,
# the largest count of any tile the chip has compiled (forward, grad and
# residuals) and run; at a count of 15.0 MiB its compiler refused (512, 2048,
# 1024) for a 256-wide f32 head, over the limit by 76 KB (PERF.md §6, PR 35).
_FLASH_VMEM_BUDGET = 59 * 2**18
# The fused-edit kernel's count (``edit_block``) is another one: it keeps the
# 14 MiB its sites were compiled under.
_EDIT_VMEM_BUDGET = 14 * 2**20

# The geometry table, (block_q, block_k_major, block_k), from the v5e sweeps
# (PERF.md §6, PR 28 and PR 29 with f32 operands, PR 35 with bfloat16 ones,
# which is what ``flash_attention_tpu`` hands the kernel for f32 arrays; f32
# operands still reach ``flash_attention_residuals``). A length's row is a
# preference order; the guard picks by the operands' width. At 4096 keys a
# head's K and V stay resident in VMEM (block_k_major == S) under a small q
# block; a resident geometry is for that length only, since elsewhere each q
# block would fetch K and V again. SD-2.1's lengths are 9 x 2^n and tile by
# none of the general geometries but the slowest: 2304 keys (48²) take K and
# V resident under a q block of 768 (1.29 ms a site against 2.72 at (256,
# 256, 256)); at 9216 keys (96²) K and V do not fit, every q block streams
# them, and the fewest q blocks that leave room for the largest score tile
# win: 4608 keys at 2 B (6.39 ms a site against 6.51), 3072 at 4 B (6.65; a
# q block of 256 reads K and V twice as often, 10.2). Any other length takes
# the first of the general geometries that tiles it. All of them pass
# through the VMEM guard, so a wide head steps down the list.
_FLASH_BY_SEQ = {
    4096: ((256, 4096, 2048),),
    9216: ((512, 4608, 1536), (512, 3072, 1536)),
    2304: ((768, 2304, 1152),),
}
_FLASH_GEOMETRIES = (
    (512, 2048, 1024),
    (1024, 1024, 1024),
    (512, 512, 512),
    (256, 256, 256),
)

# Below this many keys the einsum chain is the faster of the two (same sweep).
_FLASH_MIN_SEQ = 1024


def _flash_vmem_bytes(geometry, head_dim: int, itemsize: int) -> int:
    """The forward kernel's scoped-VMEM footprint per grid step, from above:
    q, k, v and out blocks double-buffered in the input dtype with the head
    lane-padded to 128, f32 m / l / accumulator scratch, and the f32 score
    tiles (the library unrolls its walk over ``block_k``, so a whole
    ``block_k_major`` of scores is live; its single-step body holds scores
    and probabilities). Checked against what the chip's compiler accepts and
    refuses in tests/test_chip_compile.py."""
    block_q, block_k_major, block_k = geometry
    lanes = -(-head_dim // 128) * 128
    blocks = 2 * itemsize * lanes * (2 * block_q + 2 * block_k_major)
    scratch = 4 * block_q * (lanes + 2 * 128)
    scores = 4 * block_q * block_k_major * (2 if block_k == block_k_major else 1)
    return blocks + scratch + scores


def flash_block(seq_len: int, head_dim: int, itemsize: int):
    """The flash kernel's ``(block_q, block_k_major, block_k)`` for a
    self-attention site of ``seq_len`` keys, or None where the site keeps
    the einsum chain: fewer than ``_FLASH_MIN_SEQ`` keys, a length no
    geometry tiles (the kernel requires seq_len % block == 0), or a head the
    kernel does not take or so wide that no geometry fits scoped VMEM. A
    geometry over the budget is never returned. The shape arguments are
    deliberately required: a default would make the VMEM guard opt-in, and a
    wide-head f32 call site (the VAE mid-attention shape) that omitted them
    would compile-time-OOM scoped VMEM on the chip."""
    # The library's online-softmax body takes heads up to 128 wide or whole
    # multiples of 128 (it raises on 160, SD-1.4's width at a 1024² image's
    # 32² sites).
    if seq_len < _FLASH_MIN_SEQ or (head_dim > 128 and head_dim % 128):
        return None
    for geometry in _FLASH_BY_SEQ.get(seq_len, ()) + _FLASH_GEOMETRIES:
        if (all(seq_len % b == 0 for b in geometry) and _flash_vmem_bytes(
                geometry, head_dim, itemsize) <= _FLASH_VMEM_BUDGET):
            return geometry
    return None


def takes_flash_kernel(seq_len: int, head_dim: int, itemsize: int) -> bool:
    """Whether ``fused_attention`` runs an unmasked self-attention site of
    this shape on the flash kernel in this process (it lowers on TPU only)."""
    return _on_tpu() and flash_block(seq_len, head_dim, itemsize) is not None


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
    ``(Kp, Kp)`` f32 edit transform, and f32 matmul accumulators, held to
    ``_EDIT_VMEM_BUDGET`` (14 of the 16 MiB of scoped VMEM)."""
    kp = max(128, -(-key_len // 128) * 128)

    def vmem(bq: int) -> int:
        return (3 * bq * head_dim * itemsize + 3 * kp * head_dim * itemsize
                + 3 * bq * kp * 4 + kp * kp * 4 + 2 * bq * head_dim * 4)

    for bq in (512, 256, 128):
        if pixels % bq == 0 and vmem(bq) <= _EDIT_VMEM_BUDGET:
            return bq
    # Small or non-power-of-two maps (edited self sites, tiny test configs):
    # one block over the whole query axis if it fits.
    if pixels < 128 or all(pixels % bq for bq in (512, 256, 128)):
        if vmem(pixels) <= _EDIT_VMEM_BUDGET:
            return pixels
    return 0


# The GEGLU kernel (``kernels.geglu``) asks the compiler for more scoped VMEM
# than the 16 MiB a kernel gets by default (the v5e has 128 MiB of it): a
# row tile's f32 residual and output stay resident across the walk over the
# inner width. ``ff_block`` never answers a tile it counts over the budget.
_FF_VMEM_LIMIT = 64 * 2**20
_FF_VMEM_BUDGET = 48 * 2**20

# (pixels, channels, inner) of one image's block → (row tile, inner chunk)
# from a sweep on the v5e, where the kernel ran the block faster than XLA's
# two fusions and its cell ran faster end to end (PERF.md §6): `sdxl`'s two
# levels. Any other block keeps the formula, `sd14`'s and `sd21`'s among
# them: the sweep has the kernel faster there too, but at `sdxl` the levels
# it runs at gave a third of the gain back in the operations around it, so
# a block's own time does not settle a cell. Keyed per image, so the batch
# (prompts, CFG, a serve pool's slots) does not change the decision.
_FF_BY_SHAPE = {
    (1024, 1280, 5120): (256, 1280),
    (4096, 640, 2560): (1024, 512),
}


def _ff_vmem_bytes(tile, channels: int, itemsize: int) -> int:
    """The GEGLU kernel's scoped-VMEM footprint per grid step, from above:
    the normed rows, the value, gate and ``ff_out`` weight tiles and the
    bias rows double-buffered (channels lane-padded to 128, rows of one
    sublane-padded to 8), the f32 residual and output tiles double-buffered,
    and the chunk's f32 value, gate, GELU and product tiles with the product
    narrowed and the chunk's f32 ``ff_out`` partial."""
    rows, chunk = tile
    lanes = -(-channels // 128) * 128
    blocks = 2 * (rows * lanes * itemsize + 3 * lanes * chunk * itemsize
                  + 2 * 8 * chunk * 4 + 8 * lanes * 4 + 2 * rows * lanes * 4)
    chunk_tiles = rows * chunk * (4 * 4 + itemsize) + rows * lanes * 4
    return blocks + chunk_tiles


def ff_block(pixels: int, channels: int, inner: int, itemsize: int):
    """The GEGLU kernel's ``(row tile, inner chunk)`` for a transformer
    block's feed-forward over ``pixels`` tokens an image, ``channels`` wide
    with an inner width ``inner``, its operands ``itemsize`` bytes wide;
    None where the block keeps XLA's formula: a shape not in the table, or a
    tile that does not divide the inner width or fit the VMEM budget. The
    caller checks that the row tile divides its rows (batch × pixels)."""
    tile = _FF_BY_SHAPE.get((pixels, channels, inner))
    if (tile is None or inner % tile[1]
            or _ff_vmem_bytes(tile, channels, itemsize) > _FF_VMEM_BUDGET):
        return None
    return tile


def _flash_block_sizes(geometry):
    """The one BlockSizes every flash call site uses for a ``flash_block``
    geometry — forward and residuals variants must stay on the same tiling.

    ALL backward blocks (dkv AND dq passes) must be specified or
    differentiating any program containing the kernel raises at trace time
    ("not all backward blocks are specified") — null-text inversion
    backprops through the U-Net's flash sites, which is exactly how
    this surfaced on chip. The backward passes hold more live
    tiles than the forward, so they get a capped block: the largest of
    512, 384, 256, 128 that divides ``block_k_major`` and with it the
    length (512 tiles no multiple of 2304 = 18 x 128, SD-2.1's 48² site;
    the described chip refused it: "q_seq_len should be divisible by
    block_q_major_dkv"). Correctness of the spec is pinned by an
    interpret-mode grad test (tests/test_flash_pallas.py)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    block_q, block_k_major, block_k = geometry
    bwd = next(b for b in (512, 384, 256, 128) if block_k_major % b == 0)
    return _fa.BlockSizes(
        block_q=block_q, block_k_major=block_k_major, block_k=block_k,
        block_b=1,
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


def flash_operand_dtype(dtype) -> jnp.dtype:
    """The dtype in which ``flash_attention_tpu`` hands the kernel arrays of
    ``dtype``, and so the width ``flash_block`` is asked at for such a site.

    The kernel's two products carry no precision attribute, so at the
    process's default matmul precision the MXU multiplies float32 operands
    as bfloat16: the kernel rounded them itself, K and V at every q block,
    after streaming them from HBM at twice the bytes. They are rounded once
    in front of it instead. A process that asked for more
    (``jax_default_matmul_precision`` above the default reaches the kernel's
    products too) and arrays of any other dtype keep their own."""
    name = jax.config.jax_default_matmul_precision
    try:
        default = name is None or (
            jax.lax.Precision(name) == jax.lax.Precision.DEFAULT)
    except ValueError:      # a dot algorithm by name: somebody chose
        default = False
    if default and dtype == jnp.float32:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array,
                        scale: float, geometry) -> jax.Array:
    """The Pallas TPU flash kernel call `fused_attention` takes at the
    untouched self-attention sites, tiled by ``geometry`` (a ``flash_block``
    answer). ``scale`` is folded into ``q`` ahead of the kernel, where XLA
    fuses it into the head split, so the kernel (``sm_scale=1``) makes one
    pass fewer over every score tile. The kernel takes ``q * scale``, ``k``
    and ``v`` in ``flash_operand_dtype`` of the arrays' and its output comes
    back in the arrays' own: the casts fuse into the head split and the merge
    around the site, softmax statistics and accumulator stay f32 inside.
    Kept as a named function so the CPU suite can run the identical code
    under `pltpu.force_tpu_interpret_mode()` (tests/test_flash_pallas.py) —
    the kernel otherwise only executes on a TPU."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    operand = flash_operand_dtype(q.dtype)

    def kernel(q, k, v):
        out = _fa.flash_attention(
            (q * scale).astype(operand), k.astype(operand), v.astype(operand),
            causal=False, sm_scale=1.0,
            block_sizes=_flash_block_sizes(geometry))
        return out.astype(q.dtype)

    return per_device(kernel)(q, k, v)


def flash_attention_residuals(q: jax.Array, k: jax.Array, v: jax.Array,
                              scale: float, geometry):
    """Flash kernel returning ``(out, l, m)`` — the normalized output plus
    per-row softmax statistics (sum ``l`` and max ``m`` of the local logits,
    ``scale`` included: folded into ``q`` as in ``flash_attention_tpu``).
    These are the pieces ring attention needs to merge partial results across
    devices without ever materializing local (Sq, Sk) scores
    (`parallel/ring.py`). Semantics pinned by tests/test_flash_pallas.py in
    interpret mode."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    return _fa._flash_attention(q * scale, k, v, None, None, True, False, 1.0,
                                _flash_block_sizes(geometry), False)


def _on_tpu() -> bool:
    """Static platform gate: the Pallas flash kernel only lowers on TPU
    (tests run on the CPU backend and take the einsum path)."""
    return jax.default_backend() == "tpu"
