"""What the text side hands the denoiser for a batch of prompts, as one value.

A preset with one text tower conditions on the tower's hidden states alone,
and its conditioning is that array, ``(B, L, D)``, as it always was. A preset
whose U-Net also embeds a pooled text vector beside the time step (SDXL's
``text_time`` embedding) conditions on a :class:`Conditioning`, and a
transformer denoiser that attends to a caption under a key mask (PixArt's)
on a :class:`Caption`. Each is a
pytree whose leaves share their leading axes (the prompts of a batch, and
above them whatever the caller stacked: CFG halves, groups, lanes), so every
place the value travels through (the CFG concatenation, ``sweep``'s stacking,
the serve layer's text cache and hand-off, a mesh's staging) maps over its
leaves with ``jax.tree.map`` and needs to know no more.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Conditioning(NamedTuple):
    """``context`` (B, L, D): the towers' hidden states, concatenated over
    the feature axis. ``pooled`` (B, P): the pooled text of the tower that
    has a projection. ``added`` (B, E), None as ``encode_prompts`` returns
    it: the U-Net's embedding of ``pooled`` and the sizes, which does not
    depend on the step. A caller that runs the U-Net in a loop fills it
    once ahead of the loop (``unet.embed_added``); ``apply_unet`` computes it
    where it is None."""

    context: jax.Array
    pooled: jax.Array
    added: Optional[jax.Array] = None


class Caption(NamedTuple):
    """``context`` (B, L, D): the text tower's hidden states. ``mask`` (B, L)
    int32: 1 at the keys the cross-attention sites attend to (every token up
    to and including the first end-of-text id), 0 at the padding; not a
    floating leaf, so a cast of the conditioning's floats leaves it as it
    is. ``kv``, None as ``encode_prompts`` returns it: per block of the
    denoiser the keys and values of its cross site, which do not depend on
    the step; a caller that runs the denoiser in a loop fills them once
    ahead of the loop (``transformer.embed_caption``)."""

    context: jax.Array
    mask: jax.Array
    kv: Optional[tuple] = None


def context_of(cond) -> jax.Array:
    """The hidden states the cross-attention sites read."""
    return cond.context if isinstance(cond, (Conditioning, Caption)) else cond


def with_context(cond, context: jax.Array):
    """``cond`` with other hidden states and everything else as it is (what
    null-text inversion optimises is the context alone)."""
    if isinstance(cond, (Conditioning, Caption)):
        return cond._replace(context=context)
    return context


def live_keys(cond) -> Optional[tuple]:
    """The caption keys each row attends to, counted from its mask on the
    host; None for a conditioning without a key mask."""
    if not isinstance(cond, Caption):
        return None
    return tuple(int(n) for n in np.asarray(cond.mask).sum(axis=-1))


def cfg_rows(uncond, cond):
    """``[uncond; cond]`` over the leading axis of every leaf: the batch a
    classifier-free-guidance step runs the U-Net on."""
    return jax.tree.map(lambda u, c: jnp.concatenate([u, c], axis=0), uncond, cond)


def rows(cond) -> int:
    """The length of the leading axis."""
    return context_of(cond).shape[0]


def zeros_for(cfg, b: int, dtype=jnp.float32):
    """Zeros in the form ``encode_prompts`` gives ``b`` prompts of the preset
    ``cfg`` (a ``PipelineConfig``): what a program is warmed up or a spill
    validated against before any prompt was encoded."""
    if getattr(cfg.unet, "kind", "unet") == "transformer":
        return Caption(
            jnp.zeros((b, cfg.unet.context_len, cfg.unet.caption_channels), dtype),
            jnp.ones((b, cfg.unet.context_len), jnp.int32))
    context = jnp.zeros((b, cfg.unet.context_len, cfg.unet.context_dim), dtype)
    if cfg.unet.addition_embed_in is None:
        return context
    width, = (t.projection_dim for t in cfg.towers if t.projection_dim is not None)
    return Conditioning(context, jnp.zeros((b, width), dtype))
