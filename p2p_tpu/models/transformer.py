"""Transformer denoiser over patch tokens — PixArt's DiT with adaLN-single.

Topology matches diffusers' ``PixArtTransformer2DModel`` with
``norm_type="ada_norm_single"`` (Chen et al., PixArt-α, arXiv 2310.00426,
section 2.3; PixArt-Σ, arXiv 2403.04692): the latent is cut into
``patch_size``² patches by a strided convolution and given fixed 2-D sin-cos
positions; one timestep MLP feeds every block six modulation rows through a
learned per-block table; each block runs self-attention on the modulated
LayerNorm of its tokens, cross-attention from its (unnormalised) tokens to
the caption under the caption's key mask, and a GELU-tanh feed-forward on
the second modulated LayerNorm; a final modulated layer projects each token
back to its patch of ``out_channels``. With a learned variance the first
``in_channels`` of those are ε and the rest are dropped.

The prompt-to-prompt integration is the U-Net's: every attention site has a
static ``AttnMeta`` from :func:`config.transformer_attn_specs` (block ``i``'s
self site is ``2i``, its cross site ``2i + 1``), and the site's core is
``unet.attention_core``, so the controller edits, injects and stores here
exactly as there. All sites stand at the one token grid, so the self window
(``EditParams.self_max_pixels``) holds all of them or none.

Scopes (docs/OBSERVABILITY.md, "Scope vocabulary"): ``dit/{patch_embed,
time_embed, caption_proj, final}`` and ``dit/block<i>/{self_attn/block<2i>,
cross_attn/block<2i+1>}/{qkv,core,out}``, ``dit/block<i>/ff`` and
``dit/block<i>/modulate``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..controllers.base import AttnLayout, Controller, StoreState
from .conditioning import Caption
from .config import TransformerConfig, transformer_layout
from . import nn
from .unet import _HookCtx, attention_core

Params = Dict[str, Any]

#: Diffusers' additive bias at a caption key past the mask.
MASK_BIAS = -10000.0


def _table(key, rows: int, width: int) -> jax.Array:
    """An adaLN-single table: normal over sqrt(width), as diffusers draws it."""
    return jax.random.normal(key, (rows, width), jnp.float32) * width ** -0.5


def _attn_init(key, width: int, kv_in: int, bias: bool, kd) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "to_q": nn.linear_init(k1, width, width, bias=bias, kernel_dtype=kd),
        "to_k": nn.linear_init(k2, kv_in, width, bias=bias, kernel_dtype=kd),
        "to_v": nn.linear_init(k3, kv_in, width, bias=bias, kernel_dtype=kd),
        "to_out": nn.linear_init(k4, width, width, kernel_dtype=kd),
    }


def init_transformer(key: jax.Array, cfg: TransformerConfig) -> Params:
    """Random-init parameter pytree with PixArt's shapes, kernels stored in
    ``cfg.kernel_dtype``. The positions are fixed and not parameters."""
    c, p, kd = cfg.width, cfg.patch_size, cfg.kernel_dtype
    keys = iter(jax.random.split(key, 8 + 6 * cfg.num_layers))
    blocks = []
    for _ in range(cfg.num_layers):
        blocks.append({
            "self_attn": _attn_init(next(keys), c, c, cfg.attention_bias, kd),
            "cross_attn": _attn_init(next(keys), c, cfg.context_dim,
                                     cfg.attention_bias, kd),
            "ff_in": nn.linear_init(next(keys), c, c * cfg.ff_mult,
                                    kernel_dtype=kd),
            "ff_out": nn.linear_init(next(keys), c * cfg.ff_mult, c,
                                     kernel_dtype=kd),
            "scale_shift_table": _table(next(keys), 6, c),
        })
    return {
        "patch_embed": nn.conv_init(next(keys), cfg.in_channels, c, kernel=p,
                                    kernel_dtype=kd),
        "time_embed": {
            "linear_1": nn.linear_init(next(keys), cfg.freq_dim, c,
                                       kernel_dtype=kd),
            "linear_2": nn.linear_init(next(keys), c, c, kernel_dtype=kd)},
        "t_block": nn.linear_init(next(keys), c, 6 * c, kernel_dtype=kd),
        "caption_proj": {
            "linear_1": nn.linear_init(next(keys), cfg.caption_channels, c,
                                       kernel_dtype=kd),
            "linear_2": nn.linear_init(next(keys), c, cfg.context_dim,
                                       kernel_dtype=kd)},
        "blocks": blocks,
        "final": {
            "scale_shift_table": _table(next(keys), 2, c),
            "proj_out": nn.linear_init(next(keys), c, p * p * cfg.out_channels,
                                       kernel_dtype=kd)},
    }


def sincos_positions(cfg: TransformerConfig, dtype=jnp.float32) -> jax.Array:
    """``(grid², width)`` fixed 2-D sin-cos positions (diffusers'
    ``get_2d_sincos_pos_embed`` on a grid of ``cfg.grid`` with ``base_size``
    the grid and ``cfg.interpolation_scale``): for the token in row ``i``
    and column ``j`` the first half embeds the column's coordinate and the
    second the row's, each ``[sin | cos]`` of the coordinate times
    ``10000^(-k / (width / 4))``. Computed in the program, not held as a
    constant of ``grid² × width`` numbers in its text."""
    n, quarter = cfg.grid, cfg.width // 4
    coord = jnp.arange(n, dtype=jnp.float32) / cfg.interpolation_scale
    omega = 1.0 / 10000.0 ** (jnp.arange(quarter, dtype=jnp.float32) / quarter)

    def one_axis(pos):
        out = pos.reshape(-1)[:, None] * omega[None]
        return jnp.concatenate([jnp.sin(out), jnp.cos(out)], axis=1)

    cols = jnp.broadcast_to(coord[None, :], (n, n))
    rows = jnp.broadcast_to(coord[:, None], (n, n))
    return jnp.concatenate([one_axis(cols), one_axis(rows)], axis=1).astype(dtype)


def _layer_norm(x: jax.Array) -> jax.Array:
    """LayerNorm without an affine, eps 1e-6 (PixArt's ``norm1``, ``norm2``
    and ``norm_out``)."""
    return nn.layer_norm({"scale": jnp.ones((), x.dtype),
                          "bias": jnp.zeros((), x.dtype)}, x, eps=1e-6)


def _split_heads(t: jax.Array, heads: int) -> jax.Array:
    b, n, c = t.shape
    return t.reshape(b, n, heads, c // heads).transpose(0, 2, 1, 3)


def embed_caption(params: Params, cfg: TransformerConfig, cond: Caption
                  ) -> Caption:
    """``cond`` with what the blocks read of the caption filled in
    (``Caption.kv``): the caption projected, ``c = L2(gelu_tanh(L1 T5))``,
    and each block's cross-attention keys and values of it. None of it
    depends on the step, so a sampler calls this once, ahead of its scan; a
    caption that has them comes back as it is."""
    if cond.kv is not None:
        return cond
    with jax.named_scope("dit/caption_proj"):
        proj = params["caption_proj"]
        context = cond.context
        c = nn.linear(proj["linear_2"], jax.nn.gelu(
            nn.linear(proj["linear_1"], context), approximate=True))
    kv = []
    for i, block in enumerate(params["blocks"]):
        with jax.named_scope(f"dit/block{i}/cross_attn/block{2 * i + 1}/qkv"):
            p = block["cross_attn"]
            kv.append((nn.linear(p["to_k"], c), nn.linear(p["to_v"], c)))
    return cond._replace(kv=tuple(kv))


def _self_site(p: Params, x: jax.Array, shift: jax.Array, scale: jax.Array,
               gate: jax.Array, ctx: _HookCtx, heads: int) -> jax.Array:
    """``x + gate · SelfAttn(LN(x) · (1 + scale) + shift)``."""
    meta = ctx.next_meta()
    assert not meta.is_cross, f"layout order mismatch at site {meta.layer_idx}"
    with jax.named_scope(f"self_attn/{meta.place}{meta.layer_idx}"):
        with jax.named_scope("qkv"):
            b, pix, c = x.shape
            normed = _layer_norm(x) * (1 + scale) + shift
            q, k, v = (_split_heads(nn.linear(p[n], normed), heads)
                       for n in ("to_q", "to_k", "to_v"))
        with jax.named_scope("core"):
            out = attention_core(ctx, meta, q, k, v, q.shape[-1] ** -0.5)
        with jax.named_scope("out"):
            out = out.transpose(0, 2, 1, 3).reshape(b, pix, c)
            return x + gate * nn.linear(p["to_out"], out)


def _cross_site(p: Params, x: jax.Array, kv: Tuple[jax.Array, jax.Array],
                bias: jax.Array, ctx: _HookCtx, heads: int) -> jax.Array:
    """``x + CrossAttn(x, caption)``: the tokens are not normalised (PixArt
    skips ``norm2`` here), keys and values come ready from
    :func:`embed_caption`, ``bias`` masks the caption's padding."""
    meta = ctx.next_meta()
    assert meta.is_cross, f"layout order mismatch at site {meta.layer_idx}"
    with jax.named_scope(f"cross_attn/{meta.place}{meta.layer_idx}"):
        with jax.named_scope("qkv"):
            b, pix, c = x.shape
            q = _split_heads(nn.linear(p["to_q"], x), heads)
            k, v = (_split_heads(t.astype(x.dtype), heads) for t in kv)
        with jax.named_scope("core"):
            out = attention_core(ctx, meta, q, k, v, q.shape[-1] ** -0.5, bias)
        with jax.named_scope("out"):
            out = out.transpose(0, 2, 1, 3).reshape(b, pix, c)
            return x + nn.linear(p["to_out"], out)


def apply_transformer(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,                  # (B, H, W, C) latents, NHWC
    t: jax.Array,                  # scalar or (B,) timestep
    cond: Caption,
    layout: Optional[AttnLayout] = None,
    controller: Optional[Controller] = None,
    state: StoreState = (),
    step: Optional[jax.Array] = None,
) -> Tuple[jax.Array, StoreState]:
    """Predict ε(x_t, t, caption). Returns ``(eps, controller_store_state)``
    as ``unet.apply_unet`` does, ``eps`` the first ``cfg.in_channels`` of
    the ``cfg.out_channels`` the final layer computes.

    ``cond`` is the caption (``models.conditioning.Caption``): the text
    tower's hidden states and their key mask, and the projected keys and
    values where :func:`embed_caption` filled them (else computed here)."""
    if layout is None:
        layout = transformer_layout(cfg)
    if step is None:
        step = jnp.int32(0)
    ctx = _HookCtx(layout, controller, state, step,
                   ("off",) * len(layout.metas))
    cond = embed_caption(params, cfg, cond)
    b, size, _, _ = x.shape
    p, c, heads, n = cfg.patch_size, cfg.width, cfg.num_heads, cfg.grid
    dtype = x.dtype
    # Masked caption keys take diffusers' additive -10000 before the softmax.
    bias = ((1 - cond.mask.astype(jnp.float32)) * MASK_BIAS)[:, None, None, :]

    with jax.named_scope("dit"):
        with jax.named_scope("patch_embed"):
            h = nn.conv2d(params["patch_embed"], x, stride=p, padding="VALID")
            h = h.reshape(b, n * n, c) + sincos_positions(cfg, dtype)
        with jax.named_scope("time_embed"):
            t = jnp.broadcast_to(jnp.asarray(t), (b,))
            temb = nn.timestep_embedding(t, cfg.freq_dim, dtype=dtype)
            te = params["time_embed"]
            temb = nn.linear(te["linear_2"], nn.silu(nn.linear(te["linear_1"],
                                                               temb)))
            t6 = nn.linear(params["t_block"], nn.silu(temb)).reshape(b, 6, c)

        for i, block in enumerate(params["blocks"]):
            with jax.named_scope(f"block{i}"):
                with jax.named_scope("modulate"):
                    mod = block["scale_shift_table"].astype(dtype) + t6
                    sh1, sc1, g1, sh2, sc2, g2 = (
                        mod[:, j:j + 1] for j in range(6))
                h = _self_site(block["self_attn"], h, sh1, sc1, g1, ctx, heads)
                h = _cross_site(block["cross_attn"], h, cond.kv[i], bias, ctx,
                                heads)
                with jax.named_scope("ff"):
                    normed = _layer_norm(h) * (1 + sc2) + sh2
                    hidden = jax.nn.gelu(nn.linear(block["ff_in"], normed),
                                         approximate=True)
                    h = h + g2 * nn.linear(block["ff_out"], hidden)

        assert ctx.cursor == len(layout.metas), (
            f"attention layout mismatch: model has {ctx.cursor} sites, "
            f"layout has {len(layout.metas)}")

        with jax.named_scope("final"):
            table = params["final"]["scale_shift_table"].astype(dtype)
            shift, scale = table[0] + temb[:, None], table[1] + temb[:, None]
            h = _layer_norm(h) * (1 + scale) + shift
            out = nn.linear(params["final"]["proj_out"], h)
            # unpatchify: token (i, j)'s (p, p, channels) block to its pixels
            out = out.reshape(b, n, n, p, p, cfg.out_channels)
            out = out.transpose(0, 1, 3, 2, 4, 5).reshape(
                b, size, size, cfg.out_channels)
            eps = out[..., :cfg.in_channels]     # the learned σ is dropped
    return eps, ctx.state
