"""Text encoders: CLIP-style causal transformer (SD) and BERT-style (LDM-256).

The reference consumes text encoders purely as ``ids -> (B, 77, D) hidden
states``: CLIP ViT-L/14's last hidden state for SD
(`/root/reference/ptp_utils.py:151-156`) and `model.bert` for LDM-256
(`/root/reference/ptp_utils.py:113-118`). One config-driven transformer covers
both: ``causal=True, quick_gelu`` is CLIP-L; ``causal=False, gelu`` is the
LDM's BERT-style encoder.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import TextEncoderConfig
from . import nn

Params = Dict[str, Any]


def init_text_encoder(key: jax.Array, cfg: TextEncoderConfig) -> Params:
    keys = iter(jax.random.split(key, 4 + cfg.num_layers))
    d = cfg.hidden_dim
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
            "q": nn.linear_init(k1, d, inner, bias=cfg.attn_qkv_bias),
            "k": nn.linear_init(k2, d, inner, bias=cfg.attn_qkv_bias),
            "v": nn.linear_init(k3, d, inner, bias=cfg.attn_qkv_bias),
            "out": nn.linear_init(k4, inner, d),
            "ln2": nn.norm_init(d),
            "fc1": nn.linear_init(k5, d, d * cfg.ff_mult),
            "fc2": nn.linear_init(k6, d * cfg.ff_mult, d),
        })
    return params


@jax.named_scope("text_encoder")     # docs/OBSERVABILITY.md, "Scope vocabulary"
def apply_text_encoder(params: Params, cfg: TextEncoderConfig,
                       ids: jax.Array, dtype=jnp.float32) -> jax.Array:
    """ids: (B, L) int32 → (B, L, D) final-layer hidden states (post-LN)."""
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

    for layer in params["layers"]:
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

    return nn.layer_norm(params["final_ln"], x)
