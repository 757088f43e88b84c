"""Text encoders: CLIP-style causal transformer (SD), BERT-style (LDM-256)
and T5's encoder (PixArt).

The reference consumes text encoders purely as ``ids -> (B, 77, D) hidden
states``: CLIP ViT-L/14's last hidden state for SD
(`/root/reference/ptp_utils.py:151-156`) and `model.bert` for LDM-256
(`/root/reference/ptp_utils.py:113-118`). One config-driven transformer covers
both: ``causal=True, quick_gelu`` is CLIP-L; ``causal=False, gelu`` is the
LDM's BERT-style encoder. ``arch="t5"`` is T5-v1.1's encoder (Raffel et
al., JMLR 2020; HF ``T5EncoderModel``): RMS norms without bias or mean,
attention not scaled by 1/√d with one bidirectional relative-position bias
(computed once, added in every layer together with the key mask), a
gated-GELU feed-forward and a final RMS norm.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import math

import jax
import jax.numpy as jnp
import numpy as np

from .conditioning import Conditioning
from .config import TextEncoderConfig
from . import nn

Params = Dict[str, Any]


def init_text_encoder(key: jax.Array, cfg) -> Params:
    """One tower's parameters for one tower's configuration; for a sequence
    of configurations (``PipelineConfig.text`` of a preset with several
    towers) the list of their trees, in order."""
    if isinstance(cfg, (list, tuple)):
        return [init_text_encoder(k, c)
                for k, c in zip(jax.random.split(key, len(cfg)), cfg)]
    if cfg.arch == "t5":
        return _init_t5(key, cfg)
    keys = iter(jax.random.split(key, 4 + cfg.num_layers))
    d = cfg.hidden_dim
    kd = cfg.kernel_dtype
    params: Params = {
        "token_embed": jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02,
        "pos_embed": jax.random.normal(next(keys), (cfg.max_length, d)) * 0.01,
        "layers": [],
        "final_ln": nn.norm_init(d),
    }
    inner = cfg.inner_dim
    for _ in range(cfg.num_layers):
        k1, k2, k3, k4, k5, k6 = jax.random.split(next(keys), 6)
        params["layers"].append({
            "ln1": nn.norm_init(d),
            "q": nn.linear_init(k1, d, inner, bias=cfg.attn_qkv_bias, kernel_dtype=kd),
            "k": nn.linear_init(k2, d, inner, bias=cfg.attn_qkv_bias, kernel_dtype=kd),
            "v": nn.linear_init(k3, d, inner, bias=cfg.attn_qkv_bias, kernel_dtype=kd),
            "out": nn.linear_init(k4, inner, d, kernel_dtype=kd),
            "ln2": nn.norm_init(d),
            "fc1": nn.linear_init(k5, d, d * cfg.ff_mult, kernel_dtype=kd),
            "fc2": nn.linear_init(k6, d * cfg.ff_mult, d, kernel_dtype=kd),
        })
    if cfg.projection_dim is not None:
        params["projection"] = nn.linear_init(next(keys), d, cfg.projection_dim,
                                              bias=False, kernel_dtype=kd)
    return params


def _init_t5(key: jax.Array, cfg: TextEncoderConfig) -> Params:
    """T5's encoder, its layers a list of trees (one a layer, as every
    tower's)."""
    keys = iter(jax.random.split(key, 3 + cfg.num_layers))
    d, inner, ff, kd = (cfg.hidden_dim, cfg.inner_dim, cfg.intermediate_size,
                        cfg.kernel_dtype)

    def rms():
        return {"scale": jnp.ones((d,), jnp.float32)}

    layers = []
    for _ in range(cfg.num_layers):
        k = jax.random.split(next(keys), 7)
        layers.append({
            "ln1": rms(),
            "q": nn.linear_init(k[0], d, inner, bias=False, kernel_dtype=kd),
            "k": nn.linear_init(k[1], d, inner, bias=False, kernel_dtype=kd),
            "v": nn.linear_init(k[2], d, inner, bias=False, kernel_dtype=kd),
            "out": nn.linear_init(k[3], inner, d, bias=False, kernel_dtype=kd),
            "ln2": rms(),
            "wi_0": nn.linear_init(k[4], d, ff, bias=False, kernel_dtype=kd),
            "wi_1": nn.linear_init(k[5], d, ff, bias=False, kernel_dtype=kd),
            "wo": nn.linear_init(k[6], ff, d, bias=False, kernel_dtype=kd),
        })
    return {
        "token_embed": jax.random.normal(next(keys), (cfg.vocab_size, d)),
        "relative_attention_bias": 0.5 * jax.random.normal(
            next(keys), (cfg.relative_attention_num_buckets, cfg.num_heads)),
        "layers": layers,
        "final_ln": rms(),
    }


def relative_buckets(length: int, num_buckets: int, max_distance: int
                     ) -> np.ndarray:
    """``(length, length)`` bucket of key ``j`` seen from query ``i``
    (T5's bidirectional ``_relative_position_bucket``): half the buckets for
    keys after the query, half for those at or before it; within a half,
    distances under a quarter of the buckets exactly, the rest
    logarithmically up to ``max_distance``."""
    rel = np.arange(length)[None, :] - np.arange(length)[:, None]
    half = num_buckets // 2
    buckets = (rel > 0).astype(np.int64) * half
    rel = np.abs(rel)
    exact = half // 2
    large = exact + (np.log(np.maximum(rel, 1) / exact)
                     / math.log(max_distance / exact)
                     * (half - exact)).astype(np.int64)
    large = np.minimum(large, half - 1)
    return (buckets + np.where(rel < exact, rel, large)).astype(np.int32)


def _rms_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """T5's norm: the root mean square in f32, no mean, no bias."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * p["scale"].astype(x.dtype)


def _t5(params: Params, cfg: TextEncoderConfig, ids: jax.Array,
        mask: jax.Array, dtype) -> jax.Array:
    """T5's encoder over ``ids`` (B, L) with the key ``mask`` (B, L) (1:
    attended): the final RMS norm's output (B, L, D)."""
    b, length = ids.shape
    heads = cfg.num_heads
    d_head = cfg.inner_dim // heads
    with jax.named_scope("t5"):
        x = params["token_embed"][ids].astype(dtype)
        buckets = relative_buckets(length, cfg.relative_attention_num_buckets,
                                   cfg.relative_attention_max_distance)
        position = params["relative_attention_bias"][buckets]     # (L, L, heads)
        # One bias for all layers: the positions' and the masked keys'.
        bias = (position.transpose(2, 0, 1)[None].astype(jnp.float32)
                + jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9))

        def split_heads(t):
            return t.reshape(b, length, heads, d_head).transpose(0, 2, 1, 3)

        for n, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{n}"):
                h = _rms_norm(layer["ln1"], x)
                q, k, v = (split_heads(nn.linear(layer[name], h))
                           for name in ("q", "k", "v"))
                attn = nn.fused_attention(q, k, v, 1.0, bias)     # unscaled
                attn = attn.transpose(0, 2, 1, 3).reshape(b, length, cfg.inner_dim)
                x = x + nn.linear(layer["out"], attn)
                h = _rms_norm(layer["ln2"], x)
                gate = jax.nn.gelu(nn.linear(layer["wi_0"], h), approximate=True)
                x = x + nn.linear(layer["wo"], gate * nn.linear(layer["wi_1"], h))
        return _rms_norm(params["final_ln"], x)


def _tower(params: Params, cfg: TextEncoderConfig, ids: jax.Array, dtype):
    """One tower: the hidden states it conditions with (the output of layer
    ``cfg.output_layer``, under the final LayerNorm where ``cfg.final_norm``)
    and the state after the last layer it ran. A tower with no projection
    runs no layer past the one it returns."""
    b, length = ids.shape
    x = params["token_embed"][ids].astype(dtype)
    x = x + params["pos_embed"][:length].astype(dtype)

    mask = None
    if cfg.causal:
        # Additive causal mask, f32 -inf above the diagonal (CLIP text tower).
        mask = jnp.triu(jnp.full((length, length), -1e9, jnp.float32), k=1)
        mask = mask[None, None]

    heads = cfg.num_heads
    d_head = cfg.inner_dim // heads
    scale = d_head ** -0.5

    def split_heads(t):
        return t.reshape(b, length, heads, d_head).transpose(0, 2, 1, 3)

    returned = cfg.num_layers + 1 + cfg.output_layer     # layers under the output
    layers = params["layers"]
    if cfg.projection_dim is None:                       # nothing reads the rest
        layers = layers[:returned]
    hidden = x
    for n, layer in enumerate(layers, 1):
        h = nn.layer_norm(layer["ln1"], x)
        q = split_heads(nn.linear(layer["q"], h))
        k = split_heads(nn.linear(layer["k"], h))
        v = split_heads(nn.linear(layer["v"], h))
        attn = nn.fused_attention(q, k, v, scale, mask)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, length, cfg.inner_dim)
        x = x + nn.linear(layer["out"], attn)

        h = nn.layer_norm(layer["ln2"], x)
        act = nn.quick_gelu if cfg.activation == "quick_gelu" else nn.gelu
        x = x + nn.linear(layer["fc2"], act(nn.linear(layer["fc1"], h)))
        if n == returned:
            hidden = x

    if cfg.final_norm:
        hidden = nn.layer_norm(params["final_ln"], hidden)
    return hidden, x


@jax.named_scope("text_encoder")     # docs/OBSERVABILITY.md, "Scope vocabulary"
def apply_text_encoder(params: Params, cfg: TextEncoderConfig,
                       ids: jax.Array, dtype=jnp.float32,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """ids: (B, L) int32 → (B, L, D) hidden states of one tower (the final
    layer's, post-LN, unless the configuration says otherwise). A ``t5``
    tower attends under the key ``mask`` (B, L), which it requires."""
    if cfg.arch == "t5":
        if mask is None:
            raise ValueError("a t5 tower attends under a key mask; pass mask=")
        return _t5(params, cfg, ids, mask, dtype)
    return _tower(params, cfg, ids, dtype)[0]


@jax.named_scope("text_encoder")
def apply_text_towers(params, cfgs, ids: jax.Array, eos: jax.Array,
                      dtype=jnp.float32) -> Conditioning:
    """Several towers over the same ids (B, L): their hidden states
    concatenated over the feature axis, and the pooled text of the one tower
    that has a projection: the final LayerNorm of its last layer's output at
    ``eos`` (B,) int32, each prompt's first end-of-text position, projected."""
    hidden, pooled = [], []
    for n, (p, cfg) in enumerate(zip(params, cfgs)):
        with jax.named_scope(f"tower{n}"):
            h, last = _tower(p, cfg, ids, dtype)
        hidden.append(h)
        if cfg.projection_dim is not None:
            with jax.named_scope("pool"):
                at_eos = last[jnp.arange(last.shape[0]), eos]
                pooled.append(nn.linear(
                    p["projection"], nn.layer_norm(p["final_ln"], at_eos)))
    if len(pooled) != 1:
        raise ValueError(f"{len(pooled)} of {len(cfgs)} towers have a "
                         "projection: the pooled text is one tower's")
    return Conditioning(jnp.concatenate(hidden, axis=-1), pooled[0])
