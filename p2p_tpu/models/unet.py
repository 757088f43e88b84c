"""Conditional U-Net with attention-processor injection — the denoising model.

Topology matches diffusers' `UNet2DConditionModel` as configured for SD-v1.4
(the model the reference drives, `/root/reference/main.py:29`): conv_in →
attentive down blocks → mid → attentive up blocks with skip concats → conv_out,
where every transformer block holds a self- and a cross-attention site.

The prompt-to-prompt integration point is designed in, not monkey-patched
(`/root/reference/ptp_utils.py:175-242` is the behavior spec): every attention
site has a static :class:`AttnMeta`, and :func:`apply_unet` threads the
controller's store state through the sites in call order. Sites the controller
provably never touches (``controller_touches`` is False) run fused attention —
no probability tensor exists in the compiled program; touched sites
materialize f32 probabilities, route them through
``apply_attention_control``, then finish ``probs @ v`` — except a self site
the controller only injects into (``controller_only_injects``) whose shape
the flash kernel takes, which runs the kernel on the base row's q and k in
its edit rows. Touched means edited
(every cross site under an edit, self sites up to ``self_max_pixels``) or
stored, and a site is stored only where the layout gives it a slot: for a
reader of the store (``AttnLayout.for_readers``: the caller under
``return_store=True``, LocalBlend's cross maps), never for
``Controller.store`` alone.

All tensors NHWC; params f32; compute dtype is the caller's (`x.dtype`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..controllers.base import (
    AttnLayout,
    Controller,
    StoreState,
    apply_attention_control,
    controller_only_injects,
    controller_touches,
)
from ..controllers.edit import inject_self_operands
from ..kernels import geglu
from ..obs import launches
from .conditioning import Conditioning, context_of
from .config import UNetConfig, unet_layout
from . import nn

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _attn_init(key, query_dim: int, context_dim: int, inner_dim: int,
               kd="float32") -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "to_q": nn.linear_init(k1, query_dim, inner_dim, bias=False, kernel_dtype=kd),
        "to_k": nn.linear_init(k2, context_dim, inner_dim, bias=False, kernel_dtype=kd),
        "to_v": nn.linear_init(k3, context_dim, inner_dim, bias=False, kernel_dtype=kd),
        "to_out": nn.linear_init(k4, inner_dim, query_dim, kernel_dtype=kd),
    }


def _transformer_block_init(key, dim: int, context_dim: int, ff_mult: int,
                            kd="float32") -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    ff_inner = dim * ff_mult
    return {
        "ln1": nn.norm_init(dim),
        "attn1": _attn_init(k1, dim, dim, dim, kd),
        "ln2": nn.norm_init(dim),
        "attn2": _attn_init(k2, dim, context_dim, dim, kd),
        "ln3": nn.norm_init(dim),
        # GEGLU: one projection to 2·ff_inner (value ‖ gate), then back.
        "ff_in": nn.linear_init(jax.random.split(k3)[0], dim, ff_inner * 2,
                                kernel_dtype=kd),
        "ff_out": nn.linear_init(jax.random.split(k3)[1], ff_inner, dim,
                                 kernel_dtype=kd),
    }


def _spatial_transformer_init(key, ch: int, cfg: UNetConfig, depth: int) -> Params:
    """One site group: a norm and a pair of projections around ``depth``
    transformer blocks."""
    kd = cfg.kernel_dtype
    keys = jax.random.split(key, depth + 2)
    return {
        "norm": nn.norm_init(ch),
        "proj_in": nn.conv_init(keys[0], ch, ch, kernel=1, kernel_dtype=kd),
        "blocks": [
            _transformer_block_init(keys[1 + i], ch, cfg.context_dim,
                                    cfg.ff_mult, kd)
            for i in range(depth)
        ],
        "proj_out": nn.conv_init(keys[-1], ch, ch, kernel=1, kernel_dtype=kd),
    }


def _resnet_init(key, in_ch: int, out_ch: int, temb_dim: int,
                 kd="float32") -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": nn.norm_init(in_ch),
        "conv1": nn.conv_init(k1, in_ch, out_ch, kernel_dtype=kd),
        "time_proj": nn.linear_init(k2, temb_dim, out_ch, kernel_dtype=kd),
        "norm2": nn.norm_init(out_ch),
        "conv2": nn.conv_init(k3, out_ch, out_ch, kernel_dtype=kd),
    }
    if in_ch != out_ch:
        p["skip"] = nn.conv_init(k4, in_ch, out_ch, kernel=1, kernel_dtype=kd)
    return p


def init_unet(key: jax.Array, cfg: UNetConfig) -> Params:
    """Random-init parameter pytree with SD-faithful shapes, kernels stored
    in ``cfg.kernel_dtype``."""
    n_levels = cfg.levels
    keys = iter(jax.random.split(key, 64))
    ch0 = cfg.block_channels[0]
    temb = cfg.time_embed_dim
    kd = cfg.kernel_dtype

    params: Params = {
        "time_fc1": nn.linear_init(next(keys), cfg.freq_dim or ch0, temb,
                                   kernel_dtype=kd),
        "time_fc2": nn.linear_init(next(keys), temb, temb, kernel_dtype=kd),
        "conv_in": nn.conv_init(next(keys), cfg.in_channels, ch0, kernel_dtype=kd),
        "down": [],
        "up": [],
        "norm_out": nn.norm_init(ch0),
        "conv_out": nn.conv_init(next(keys), ch0, cfg.out_channels,
                                 kernel_dtype=kd),
    }

    # Down path. Skip-channel bookkeeping mirrors diffusers exactly so up-block
    # concat widths match real checkpoints.
    skip_chs = [ch0]
    in_ch = ch0
    for level in range(n_levels):
        out_ch = cfg.block_channels[level]
        depth = cfg.depth_at(level)
        block: Params = {"resnets": [], "attns": []}
        for _ in range(cfg.layers_per_block):
            block["resnets"].append(_resnet_init(next(keys), in_ch, out_ch, temb, kd))
            if depth:
                block["attns"].append(
                    _spatial_transformer_init(next(keys), out_ch, cfg, depth))
            in_ch = out_ch
            skip_chs.append(out_ch)
        if level != n_levels - 1:
            block["downsample"] = nn.conv_init(next(keys), out_ch, out_ch,
                                               kernel_dtype=kd)
            skip_chs.append(out_ch)
        params["down"].append(block)

    mid_ch = cfg.block_channels[-1]
    params["mid"] = {
        "resnet1": _resnet_init(next(keys), mid_ch, mid_ch, temb, kd),
        "attn": _spatial_transformer_init(next(keys), mid_ch, cfg, cfg.mid_depth),
        "resnet2": _resnet_init(next(keys), mid_ch, mid_ch, temb, kd),
    }

    # Up path (reverse level order).
    in_ch = mid_ch
    for level in reversed(range(n_levels)):
        out_ch = cfg.block_channels[level]
        depth = cfg.depth_at(level)
        block = {"resnets": [], "attns": []}
        for _ in range(cfg.layers_per_block + 1):
            skip_ch = skip_chs.pop()
            block["resnets"].append(
                _resnet_init(next(keys), in_ch + skip_ch, out_ch, temb, kd))
            if depth:
                block["attns"].append(
                    _spatial_transformer_init(next(keys), out_ch, cfg, depth))
            in_ch = out_ch
        if level != 0:
            block["upsample"] = nn.conv_init(next(keys), out_ch, out_ch,
                                             kernel_dtype=kd)
        params["up"].append(block)

    if cfg.addition_embed_in is not None:
        params["add_fc1"] = nn.linear_init(next(keys), cfg.addition_embed_in,
                                           temb, kernel_dtype=kd)
        params["add_fc2"] = nn.linear_init(next(keys), temb, temb,
                                           kernel_dtype=kd)
    return params


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def _apply_resnet(p: Params, x: jax.Array, temb: jax.Array, groups: int) -> jax.Array:
    h = nn.conv2d(p["conv1"], nn.silu(nn.group_norm(p["norm1"], x, groups)))
    # The time-embedding shift feeds nothing but norm2: it joins that norm's
    # (N, C) vectors and is never added at the activation's size.
    shift = nn.linear(p["time_proj"], nn.silu(temb))
    h = nn.conv2d(p["conv2"], nn.silu(
        nn.group_norm(p["norm2"], h, groups, shift=shift)))
    if "skip" in p:
        x = nn.conv2d(p["skip"], x)
    return x + h


# Phase-gated sampling's cross-attention cache: one ``(B_cond, P, C)`` array
# per cross site in call order — the attn2 *output* (post-``to_out``) of the
# conditional batch half, captured on the last phase-1 step. Consuming it in
# phase 2 removes the whole q/k/v-projection + softmax(QKᵀ)V + ``to_out``
# pipeline of every cross site from the compiled program (TAD, arXiv
# 2404.02747: cross-attention outputs converge after an early gate step).
AttnCache = Tuple[jax.Array, ...]


def init_attn_cache(layout: AttnLayout, batch_cond: int,
                    dtype=jnp.float32) -> AttnCache:
    """Zero-initialized cache buffers for every cross-attention site.

    Requires a layout whose metas carry ``channels`` (built from
    ``unet_attn_specs``); hand-built 5-tuple layouts can't size the buffers.
    """
    caches = []
    for m in layout.metas:
        if not m.is_cross:
            continue
        if m.channels <= 0:
            raise ValueError(
                f"cross site {m.layer_idx} has no channel info "
                "(layout built from 5-tuple specs); the attention cache "
                "needs channels — rebuild the layout via unet_attn_specs")
        caches.append(jnp.zeros((batch_cond, m.pixels, m.channels), dtype))
    return tuple(caches)


class _HookCtx:
    """Trace-time cursor over the attention layout, carrying the controller
    store state through the sites in call order. ``sp`` optionally names a
    mesh axis for sequence-parallel (ring) self-attention at large sites.

    ``site_plan`` is the static per-site cache action (engine.reuse), one
    mode per layout site in call order, so each plan compiles its own
    program: ``'off'`` — no cache interaction; ``'store'`` — compute the
    site and overwrite its cache slot with the conditional half of its
    output, ``'store_all'`` with the whole batch; ``'use'`` — the site
    returns its cached output directly, computing nothing. The cache cursor
    walks the non-``'off'`` sites, whose leaves ``attn_cache`` holds in the
    same order."""

    def __init__(self, layout: AttnLayout, controller: Optional[Controller],
                 state: StoreState, step: jax.Array,
                 site_plan: Tuple[str, ...],
                 sp: Optional["SpConfig"] = None,
                 attn_cache: Optional[AttnCache] = None,
                 kernels=None):
        self.layout = layout
        self.controller = controller
        self.state = state
        self.step = step
        self.sp = sp
        self.cursor = 0
        self.attn_cache = attn_cache
        self.site_plan = site_plan
        self.cross_cursor = 0
        # Fused-kernel dispatch plan (kernels.KernelConfig or None): static,
        # so each covered controller-touched site lowers to the in-kernel
        # edit program instead of the materialized f32 path.
        self.kernels = kernels

    def next_meta(self):
        meta = self.layout.metas[self.cursor]
        self.cursor += 1
        return meta


@dataclasses.dataclass(frozen=True)
class SpConfig:
    """Sequence-parallel plan for self-attention: shard the pixel axis of
    every *untouched* self site with ≥ ``min_pixels`` pixels over mesh axis
    ``axis``. This is the scaling axis the reference lacks entirely
    (SURVEY §5: resolution is quadratic in pixels); controller-touched
    sites stay local because edits read whole probability rows.

    ``mode`` selects the communication scheme: ``"ring"`` rotates k/v
    shards via ppermute (`parallel/ring.py`); ``"alltoall"`` redistributes
    to head sharding for one dense local attention per device
    (Ulysses-style, `parallel/alltoall.py`) — sites whose head count the
    axis doesn't divide fall back to the ring, which is always valid."""

    mesh: Any                 # jax.sharding.Mesh
    axis: str = "sp"
    min_pixels: int = 64 * 64
    mode: str = "ring"

    def __post_init__(self):
        if self.mode not in ("ring", "alltoall"):
            raise ValueError(f"unknown sp mode {self.mode!r} "
                             f"(expected 'ring' or 'alltoall')")


def _apply_attention(p: Params, ln: Params, x: jax.Array, context: jax.Array,
                     heads: int, ctx: _HookCtx, is_cross: bool) -> jax.Array:
    """One attention site with its pre-norm and residual:
    ``x + attn(layer_norm(x))``. x: (B, P, C); context: (B, K, Cc).

    Every site's computation is wrapped in a ``jax.named_scope`` whose
    name encodes the site identity (``cross_attn/down3`` etc. — place +
    global layer index from the :class:`AttnMeta`), with the parts
    ``qkv`` (pre-norm, projections, head split), ``core`` (scores,
    softmax, the edit, P·V — whichever of XLA / flash / fused runs it) and
    ``out`` (head merge, ``to_out``, cache store, residual) below it. The
    scope lands in the ``op_name`` metadata of the compiled program's
    instructions, which ``obs.traceparse.scope_index`` maps back from
    instruction names: that join is what splits a device trace's step time
    per site (the trace itself carries instruction names only;
    docs/OBSERVABILITY.md). A trace-time name only: the lowered ops,
    numerics and jaxpr structure are identical with or without it."""
    meta = ctx.next_meta()
    assert meta.is_cross == is_cross, (
        f"layout order mismatch at site {meta.layer_idx}: layout says "
        f"is_cross={meta.is_cross}, model called is_cross={is_cross}")
    with jax.named_scope(f"{'cross_attn' if is_cross else 'self_attn'}"
                         f"/{meta.place}{meta.layer_idx}"):
        return _attention_site(p, ln, x, context, heads, ctx, meta, is_cross)


def _fused_edit_dispatch(ctx: _HookCtx, meta, q, k, v, scale):
    """Route a controller-touched site to the fused-edit Pallas kernel
    (``kernels.fused_edit``) when the static dispatch plan covers it; None →
    the caller keeps the materialized reference path. The kernel applies the
    controller's edit inside a tiled softmax, so the ``(2B, heads, P, K)``
    probability tensor never reaches HBM at fused sites. Compiled-kernel
    lowering only exists on TPU; ``interpret=True`` configs run the
    identical program through the pallas interpreter (the CPU parity
    surface) and anything else off-TPU is a trace-time error — a kernel
    run that quietly took the materialized path would pass as a kernel
    run. Attention-STORE sites are never fused (``kernel_edit_spec``
    returns None for them — the store needs the materialized tensor)."""
    if ctx.kernels is None:
        return None
    if not (ctx.kernels.interpret or nn._on_tpu()):
        raise RuntimeError(
            f"KernelConfig without interpret=True on the "
            f"{jax.default_backend()!r} backend: the fused-edit kernel only "
            f"lowers on TPU — pass KernelConfig(interpret=True) off-chip")
    from .. import kernels as kernels_mod

    if not ctx.kernels.covers(kernels_mod.dispatch.site_name(meta)):
        return None
    from ..kernels.fused_edit import fused_site_attention

    return fused_site_attention(q, k, v, scale, ctx.controller, meta,
                                ctx.step, block_q=ctx.kernels.block_q,
                                interpret=ctx.kernels.interpret)


def attention_core(ctx: _HookCtx, meta, q: jax.Array, k: jax.Array,
                   v: jax.Array, scale: float, mask=None) -> jax.Array:
    """A site's ``softmax(q kᵀ · scale) v`` under its controller, whichever
    implementation runs it: the controller's materialized maps at a touched
    site, the flash kernel on the base row's q and k at a self site it only
    injects into, the fused-edit kernel where the dispatch plan covers the
    site, ring / all-to-all attention over a mesh, else
    ``nn.fused_attention``. q, k, v: ``(B, heads, S, D)``; ``mask``: an
    additive key bias broadcastable to ``(B, heads, P, K)`` (a caption's
    padding at a transformer's cross sites), None elsewhere. Notes each self
    site's implementation for the launch being traced."""
    is_cross = meta.is_cross
    pix, d_head = q.shape[2], q.shape[-1]
    # How nn.fused_attention runs the site, from the shape alone: the
    # tile is the one for the width the kernel is handed its operands in.
    operand = nn.flash_operand_dtype(q.dtype)
    geometry = (nn.flash_block(pix, d_head, operand.itemsize)
                if nn.takes_flash_kernel(pix, d_head, operand.itemsize)
                else None)
    how = "einsum" if geometry is None else "kernel"
    if controller_touches(ctx.controller, meta):
        how = "edited"
        out = _fused_edit_dispatch(ctx, meta, q, k, v, scale)
        if (out is None and geometry is not None
                and controller_only_injects(ctx.controller, meta)):
            # The edit rows take the base row's map: the flash kernel on
            # the base row's q and k over their own v, no map in memory.
            # Where the einsum chain would run, the map exists anyway and
            # the edit fuses into P·V, which a substitution of q and k
            # does not (PERF.md §6): such a site stays below.
            q, k = inject_self_operands(ctx.controller.edit, q, k, ctx.step)
            out = nn.fused_attention(q, k, v, scale)
        else:
            geometry = None             # the fused-edit kernel's, or none
            if out is None:
                probs = nn.attention_probs(q, k, scale, mask)  # (B, heads, P, K) f32
                ctx.state, probs = apply_attention_control(
                    ctx.controller, meta, ctx.state, probs, ctx.step)
                out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    elif (ctx.sp is not None and not is_cross
          and meta.pixels >= ctx.sp.min_pixels):
        n = ctx.sp.mesh.shape[ctx.sp.axis]
        if meta.pixels % n:
            # Unsharded fallback is safe only when fused attention stays
            # blockwise (``nn.flash_block`` has a geometry for the site).
            # Otherwise the einsum path would materialize the O(P²)
            # scores on one device — the blow-up SpConfig exists to
            # avoid — so that case is an error, not a warning.
            if nn.flash_block(meta.pixels, d_head,
                              operand.itemsize) is None:
                raise ValueError(
                    f"sequence-parallel site {meta.layer_idx} has "
                    f"{meta.pixels} pixels, not divisible by mesh axis "
                    f"{ctx.sp.axis!r}={n}, and not flash-tileable locally; "
                    f"choose a divisor axis size or raise SpConfig.min_pixels")
            import warnings

            warnings.warn(
                f"sequence-parallel site {meta.layer_idx}: {meta.pixels} "
                f"pixels not divisible by mesh axis {ctx.sp.axis!r}={n}; "
                f"running this site unsharded (local flash)", stacklevel=2)
            out = nn.fused_attention(q, k, v, scale)
        elif ctx.sp.mode == "alltoall" and q.shape[1] % n == 0:
            from ..parallel.alltoall import alltoall_self_attention

            how, geometry = "sharded", None
            out = alltoall_self_attention(q, k, v, scale, ctx.sp.mesh,
                                          ctx.sp.axis)
        else:
            if ctx.sp.mode == "alltoall":
                # Same user-visible note as the pixel-indivisible fallback
                # above: someone benchmarking alltoall must not unknowingly
                # measure ring (warnings module dedups per call site).
                import warnings

                warnings.warn(
                    f"sequence-parallel site {meta.layer_idx}: "
                    f"{q.shape[1]} heads not divisible by mesh axis "
                    f"{ctx.sp.axis!r}={n}; alltoall falls back to ring "
                    f"at this site", stacklevel=2)
            from ..parallel.ring import ring_self_attention

            how, geometry = "sharded", None
            out = ring_self_attention(q, k, v, scale, ctx.sp.mesh, ctx.sp.axis)
    else:
        out = nn.fused_attention(q, k, v, scale, mask)
    if not is_cross:
        # the tile and operand width wherever the site reached the kernel
        launches.note_self_site(meta.layer_idx, how, pix, d_head, geometry,
                                operand.name if geometry else "")
    return out


def _attention_site(p: Params, ln: Params, x: jax.Array, context: jax.Array,
                    heads: int, ctx: _HookCtx, meta, is_cross: bool) -> jax.Array:
    mode = ctx.site_plan[meta.layer_idx]
    if mode == "use":
        # The site's output is served from its cache: for cross sites the
        # text context is untouched so the cached tensor is the TAD reuse;
        # for self sites it is the A-SDM feature inherited from the site's
        # last computed step. Returning it here removes q/k/v,
        # softmax(QKᵀ)V and to_out for the site from the compiled program
        # entirely.
        cached = ctx.attn_cache[ctx.cross_cursor]
        ctx.cross_cursor += 1
        assert cached.shape == (x.shape[0], x.shape[1], x.shape[2]), (
            f"attn cache shape {cached.shape} does not match site "
            f"{meta.layer_idx} input {x.shape} — was the cache captured at a "
            "different batch/resolution?")
        with jax.named_scope("out"):
            return x + cached

    with jax.named_scope("qkv"):
        b, pix, _ = x.shape
        normed = nn.layer_norm(ln, x)
        src = context if is_cross else normed
        q = nn.linear(p["to_q"], normed)
        k = nn.linear(p["to_k"], src)
        v = nn.linear(p["to_v"], src)
        d_head = q.shape[-1] // heads
        scale = d_head ** -0.5

        def split_heads(t):
            return t.reshape(b, t.shape[1], heads, d_head).transpose(0, 2, 1, 3)

        q, k, v = split_heads(q), split_heads(k), split_heads(v)

    with jax.named_scope("core"):
        out = attention_core(ctx, meta, q, k, v, scale)

    with jax.named_scope("out"):
        out = out.transpose(0, 2, 1, 3).reshape(b, pix, heads * d_head)
        out = nn.linear(p["to_out"], out)
        if mode == "store":
            # Capture the conditional half of the CFG-doubled batch (rows B:).
            # Overwritten every step, so after the scan the cache holds
            # exactly the last stored step's outputs — no per-step select.
            lst = list(ctx.attn_cache)
            lst[ctx.cross_cursor] = out[out.shape[0] // 2:]
            ctx.attn_cache = tuple(lst)
            ctx.cross_cursor += 1
        elif mode == "store_all":
            # A site that flips to reuse inside its current batch regime
            # (engine.reuse MODE_STORE_ALL) keeps the whole live batch — 2B
            # while CFG is active, B past the gate — so the flip segment can
            # serve it without a shape change.
            lst = list(ctx.attn_cache)
            lst[ctx.cross_cursor] = out
            ctx.attn_cache = tuple(lst)
            ctx.cross_cursor += 1
        return x + out


def _apply_transformer_block(p: Params, x: jax.Array, context: jax.Array,
                             heads: int, ctx: _HookCtx) -> jax.Array:
    x = _apply_attention(p["attn1"], p["ln1"], x, context, heads, ctx,
                         is_cross=False)
    x = _apply_attention(p["attn2"], p["ln2"], x, context, heads, ctx,
                         is_cross=True)
    with jax.named_scope("ff"):
        normed = nn.layer_norm(p["ln3"], x)
        # GEGLU in one kernel where the shape, the platform and the mesh
        # allow it, else XLA's two products with the f32 (tokens, 2·inner)
        # tensor between them.
        how, tile = geglu.plan(x, p["ff_in"], p["ff_out"])
        launches.note_ff_site(ctx.cursor, how, x.size // x.shape[-1],
                              x.shape[-1], p["ff_out"]["kernel"].shape[0], tile)
        if tile is None:
            return geglu.feed_forward_formula(x, normed, p["ff_in"], p["ff_out"])
        return geglu.geglu_feed_forward(x, normed, p["ff_in"], p["ff_out"], tile)


def _apply_spatial_transformer(p: Params, x: jax.Array, context: jax.Array,
                               cfg: UNetConfig, ctx: _HookCtx) -> jax.Array:
    b, h, w, c = x.shape
    residual = x
    with jax.named_scope("proj_in"):
        x = nn.group_norm(p["norm"], x, cfg.groups, eps=1e-6)
        # proj_in/proj_out are 1×1 convs in the checkpoint; applied as linears
        # in token-major space so the whole transformer stack stays (B, P, C)
        # with no spatial relayouts between the convs and the attention
        # matmuls.
        x = x.reshape(b, h * w, c)
        x = nn.linear_1x1(p["proj_in"], x)
    for block in p["blocks"]:
        x = _apply_transformer_block(block, x, context, cfg.heads_for(c), ctx)
    with jax.named_scope("proj_out"):
        x = nn.linear_1x1(p["proj_out"], x)
        return x.reshape(b, h, w, c) + residual


def embed_added(params: Params, cfg: UNetConfig, cond):
    """``cond`` with the embedding that is added to the time embedding
    filled in (``Conditioning.added``): ``add = W2 silu(W1 a + b1) + b2`` over
    ``a = [pooled | e(s0) | ... | e(s5)]``, ``e`` the sinusoidal embedding of
    the time step at ``cfg.addition_time_dim`` and ``s`` the preset's
    ``cfg.addition_sizes``. It does not depend on the step, so a sampler
    calls this once, ahead of its scan. A conditioning that has none (a
    one-tower preset's array) or has it already comes back as it is."""
    if not isinstance(cond, Conditioning) or cond.added is not None:
        return cond
    with jax.named_scope("unet/add_embed"):
        pooled = cond.pooled
        sizes = jnp.asarray(cfg.addition_sizes, jnp.float32)
        e = nn.timestep_embedding(sizes, cfg.addition_time_dim,
                                  dtype=pooled.dtype).reshape(-1)
        a = jnp.concatenate(
            [pooled, jnp.broadcast_to(e, pooled.shape[:-1] + e.shape)], axis=-1)
        added = nn.linear(params["add_fc2"],
                          nn.silu(nn.linear(params["add_fc1"], a)))
    return cond._replace(added=added)


def apply_unet(
    params: Params,
    cfg: UNetConfig,
    x: jax.Array,                  # (B, H, W, C) latents, NHWC
    t: jax.Array,                  # scalar or (B,) timestep
    context,                       # (B, K, Cc) text embeddings, or a Conditioning
    layout: Optional[AttnLayout] = None,
    controller: Optional[Controller] = None,
    state: StoreState = (),
    step: Optional[jax.Array] = None,
    sp: Optional[SpConfig] = None,
    attn_cache: Optional[AttnCache] = None,
    site_plan: Optional[Tuple[str, ...]] = None,
    kernels=None,
):
    """Predict ε(x_t, t, context). Returns ``(eps, controller_store_state)``,
    plus the updated cache as a third element iff a ``site_plan`` is given.

    ``context`` is the preset's conditioning (``models.conditioning``): the
    text tower's hidden states, or a ``Conditioning`` of them and the pooled
    text where ``cfg.addition_embed_in`` is set, whose embedding joins the
    time embedding that every ResNet block takes.

    ``kernels`` (a static ``kernels.KernelConfig``) routes covered
    controller-touched sites to the fused-edit Pallas kernel — the edit
    runs inside a tiled softmax and the probability tensor never
    materializes in HBM (see :func:`_fused_edit_dispatch` for the exact
    dispatch conditions). ``kernels=None`` is byte-identical to the
    pre-existing program.

    With ``controller=None`` this is a plain conditional U-Net forward and the
    returned state is the input state — the `EmptyControl ≡ no controller`
    equivalence holds at the XLA-program level. ``sp`` enables ring
    (sequence-parallel) attention for large untouched self sites.

    ``site_plan`` (static; None: every site ``'off'``) is the per-site cache
    action of phase-gated sampling and reuse schedules (engine.reuse) over
    ``attn_cache``, one leaf per site that is not ``'off'``, in call order.
    The phase-1 plan of ``gate=g`` has every cross site in ``'store'``: the
    normal CFG-doubled forward, each slot overwritten with the site's
    conditional-half output. Its phase-2 plan has them in ``'use'``: the
    single-branch forward (no uncond half) with every cross site replaced
    by its cached output — a genuinely smaller program. Edits and stores at
    a ``'use'`` site are structurally impossible (no probability tensor is
    computed there): the sampler passes no controller past the CFG
    boundary, and schedule resolution warns about a site reused inside an
    edit window (engine.reuse.warn_schedule_conflicts).
    """
    if layout is None:
        layout = unet_layout(cfg)
    plan = site_plan
    if plan is None:
        plan = ("off",) * len(layout.metas)
    if len(plan) != len(layout.metas):
        raise ValueError(
            f"site_plan has {len(plan)} entries for a layout "
            f"with {len(layout.metas)} attention sites")
    bad = set(plan) - {"off", "store", "store_all", "use"}
    if bad:
        raise ValueError(f"unknown site_plan mode(s) {sorted(bad)}")
    n_cached = sum(1 for m in plan if m != "off")
    if n_cached != (0 if attn_cache is None else len(attn_cache)):
        raise ValueError(
            f"site_plan has {n_cached} cached site(s); attn_cache has "
            f"{None if attn_cache is None else len(attn_cache)} "
            "leaf/leaves")
    if step is None:
        step = jnp.int32(0)
    ctx = _HookCtx(layout, controller, state, step, plan, sp=sp,
                   attn_cache=attn_cache, kernels=kernels)
    g = cfg.groups
    if (cfg.addition_embed_in is not None) != isinstance(context, Conditioning):
        raise ValueError(
            f"a U-Net with addition_embed_in={cfg.addition_embed_in} is "
            f"conditioned on a {type(context).__name__}: one that embeds a "
            "pooled vector takes a Conditioning, any other the hidden states")
    launches.note_unet_depth(tuple(map(cfg.depth_at, range(cfg.levels))))
    added = (embed_added(params, cfg, context).added
             if isinstance(context, Conditioning) else None)
    context = context_of(context)

    # Scopes (docs/OBSERVABILITY.md, "Scope vocabulary"): ``unet/<part>`` and
    # ``unet/<place><n>/<part>``, n counting a place's blocks in the order
    # they run (diffusers' ``down_blocks.n`` / ``up_blocks.n``).
    def resnet_and_attn(block, i, h):
        with jax.named_scope(f"res{i}"):
            h = _apply_resnet(block["resnets"][i], h, temb, g)
        if block["attns"]:      # none at a level without a transformer
            with jax.named_scope(f"attn{i}"):
                h = _apply_spatial_transformer(block["attns"][i], h, context,
                                               cfg, ctx)
        return h

    with jax.named_scope("unet"):
        with jax.named_scope("time_embed"):
            t = jnp.broadcast_to(jnp.asarray(t), (x.shape[0],))
            temb = nn.timestep_embedding(
                t, cfg.freq_dim or cfg.block_channels[0], dtype=x.dtype)
            temb = nn.linear(params["time_fc2"],
                             nn.silu(nn.linear(params["time_fc1"], temb)))
            if added is not None:
                temb = temb + added

        with jax.named_scope("conv_in"):
            h = nn.conv2d(params["conv_in"], x)
        skips = [h]
        for n, block in enumerate(params["down"]):
            with jax.named_scope(f"down{n}"):
                for i in range(len(block["resnets"])):
                    h = resnet_and_attn(block, i, h)
                    skips.append(h)
                if "downsample" in block:
                    # Symmetric pad 1 (diffusers downsample_padding=1) — XLA
                    # SAME would pad (0,1) on even inputs and shift every
                    # downstream feature map.
                    with jax.named_scope("downsample"):
                        h = nn.conv2d(block["downsample"], h, stride=2,
                                      padding=1)
                    skips.append(h)

        with jax.named_scope("mid0"):
            mid = params["mid"]
            with jax.named_scope("res0"):
                h = _apply_resnet(mid["resnet1"], h, temb, g)
            with jax.named_scope("attn0"):
                h = _apply_spatial_transformer(mid["attn"], h, context, cfg,
                                               ctx)
            with jax.named_scope("res1"):
                h = _apply_resnet(mid["resnet2"], h, temb, g)

        for n, block in enumerate(params["up"]):
            with jax.named_scope(f"up{n}"):
                for i in range(len(block["resnets"])):
                    with jax.named_scope("skip_concat"):
                        h = jnp.concatenate([h, skips.pop()], axis=-1)
                    h = resnet_and_attn(block, i, h)
                if "upsample" in block:
                    with jax.named_scope("upsample"):
                        h = nn.conv2d(block["upsample"],
                                      nn.upsample_nearest_2x(h))

        assert ctx.cursor == len(layout.metas), (
            f"attention layout mismatch: model has {ctx.cursor} sites, "
            f"layout has {len(layout.metas)}")

        with jax.named_scope("conv_out"):
            h = nn.silu(nn.group_norm(params["norm_out"], h, g))
            eps = nn.conv2d(params["conv_out"], h)
    if site_plan is not None:
        return eps, ctx.state, ctx.attn_cache
    return eps, ctx.state
