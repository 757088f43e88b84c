"""LocalBlend — spatial masking of edits from stored cross-attention maps.

Behavioral spec: `/root/reference/main.py:33-66` (base) and
`/root/reference/null_text.py:39-102` (adds ``start_blend`` warm-up,
``substruct_words`` and dual thresholds). We implement the null_text
semantics — its ``mask[:1] | mask`` form (`/root/reference/null_text.py:50`)
is batch-size-general where main.py's ``mask[:1] + mask[1:]`` only broadcasts
for 2 prompts, and it degenerates to main.py's behavior for B=2 /
``start_blend=0`` / no substruct.

Layout note: latents here are NHWC ``(B, H, W, C)`` (TPU-friendly), and the
mask pipeline runs at the blend resolution (16×16 for SD-1.4) derived from the
attention layout, not hard-coded layer slices — the model-derived replacement
for the reference's ``down_cross[2:4] + up_cross[:3]`` (`main.py:37-38`).
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Union

import jax
import jax.numpy as jnp
from flax import struct

if TYPE_CHECKING:  # circular-import guard; only needed for type hints
    from .base import AttnLayout, PaperLevel


@struct.dataclass
class BlendParams:
    """Precomputed LocalBlend parameters.

    ``alpha_layers``/``substruct_layers``: ``(B, L)`` one-hot over the selected
    words' token indices per prompt (B = 1 + E includes the source prompt,
    `/root/reference/main.py:58-64`).
    """

    alpha_layers: jax.Array
    substruct_layers: Optional[jax.Array] = None
    # Scalar leaves (traced) so threshold / warm-up sweeps don't recompile.
    start_blend: jax.Array = struct.field(default_factory=lambda: jnp.int32(0))
    th_pool: jax.Array = struct.field(default_factory=lambda: jnp.float32(0.3))
    th_nopool: jax.Array = struct.field(default_factory=lambda: jnp.float32(0.3))
    # Static: selects which store slots feed the mask (a shape decision). A
    # ``base.PaperLevel`` where nobody gave one, until ``AttnLayout.resolve``.
    resolution: Union[int, "PaperLevel"] = struct.field(pytree_node=False, default=16)


def _max_pool_3x3(x: jax.Array) -> jax.Array:
    """3×3, stride-1, pad-1 max pool over the two trailing-spatial axes of
    ``(B, H, W)`` (k=1 in `/root/reference/main.py:45`)."""
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, 3, 3),
        window_strides=(1, 1, 1),
        padding=((0, 0), (1, 1), (1, 1)),
    )


def _collect_blend_maps(
    params: BlendParams, layout: "AttnLayout", state: tuple
) -> jax.Array:
    """Stack the accumulated cross-attention maps at the blend resolution:
    ``(B, S*heads, res, res, L)`` — the jit-shaped equivalent of the
    reshape+cat at `/root/reference/main.py:39-43`."""
    res = params.resolution
    maps = []
    for m in layout.blend_metas(res):
        a = state[m.store_slot]  # (B, heads, res², L)
        maps.append(a.reshape(a.shape[0], a.shape[1], res, res, a.shape[-1]))
    if not maps:
        raise ValueError(
            f"LocalBlend needs stored cross-attention maps at resolution {res} "
            "— check the layout's StoreConfig stores cross maps."
        )
    return jnp.concatenate(maps, axis=1)


def _mask_from_maps(
    maps: jax.Array, word_alpha: jax.Array, use_pool: bool, threshold: float,
    out_hw: tuple,
) -> jax.Array:
    """Word-weighted average → (pool) → upsample → per-image max-normalize →
    threshold → OR with the source image's mask
    (`/root/reference/null_text.py:41-51`). Returns bool ``(B, H, W)``."""
    # maps: (B, SH, res, res, L); word_alpha: (B, L)
    weighted = (maps * word_alpha[:, None, None, None, :]).sum(-1).mean(1)  # (B, res, res)
    if use_pool:
        weighted = _max_pool_3x3(weighted)
    mask = jax.image.resize(weighted, (weighted.shape[0],) + out_hw, method="nearest")
    denom = mask.max(axis=(1, 2), keepdims=True)
    mask = mask / jnp.maximum(denom, 1e-20)
    mask = mask > threshold
    return jnp.logical_or(mask[:1], mask)


def apply_local_blend(
    params: BlendParams,
    layout: "AttnLayout",
    state: tuple,
    x_t: jax.Array,
    step: jax.Array,
) -> jax.Array:
    """Composite edited latents onto the source latents outside the mask:
    ``x_t = x_t[:1] + mask * (x_t - x_t[:1])`` (`/root/reference/main.py:51`),
    active once ``step + 1 > start_blend`` (the counter warm-up of
    `/root/reference/null_text.py:54-55`). ``x_t``: NHWC ``(B, H, W, C)``."""
    maps = _collect_blend_maps(params, layout, state)
    hw = (x_t.shape[1], x_t.shape[2])
    mask = _mask_from_maps(maps, params.alpha_layers, True, params.th_pool, hw)
    if params.substruct_layers is not None:
        sub = _mask_from_maps(maps, params.substruct_layers, False, params.th_nopool, hw)
        mask = jnp.logical_and(mask, jnp.logical_not(sub))
    maskf = mask.astype(x_t.dtype)[..., None]  # (B, H, W, 1)
    blended = x_t[:1] + maskf * (x_t - x_t[:1])
    return jnp.where(step + 1 > params.start_blend, blended, x_t)
