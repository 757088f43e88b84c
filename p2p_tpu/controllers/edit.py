"""The cross/self attention edit algebra (Replace / Refine / Reweight).

Pure functions over ``(heads, P, K)`` base maps and ``(E, heads, P, K)`` edit
maps, parameterized by a single :class:`EditParams` pytree. The reference
spreads this over a class hierarchy (`/root/reference/main.py:162-278`); here
the three edit kinds are one static ``kind`` switch plus an optional equalizer
multiply, which also expresses the reference's controller chaining
(AttentionReweight wrapping Replace/Refine via ``prev_controller``,
`/root/reference/main.py:258-261`) as plain composition.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from flax import struct

if TYPE_CHECKING:  # circular-import guard; only needed for type hints
    from .base import PaperLevel


@struct.dataclass
class EditParams:
    """Precomputed edit parameters (all host-side, once per edit).

    Array fields (pytree leaves):
      cross_alpha  — ``(T+1, E, 1, 1, L)`` per-step/per-token blend schedule
                     (`/root/reference/ptp_utils.py:279-297`).
      mapper       — Replace: ``(E, L, L)`` float projection; Refine:
                     ``(E, L)`` int32 gather; None for pure Reweight.
      refine_alphas— Refine: ``(E, 1, 1, L)`` 0/1 "token existed in source".
      equalizer    — ``(E, L)`` per-token scales, or None.

      self_start/end — step window for self-attention injection
                     (`/root/reference/main.py:208-211`). Scalar leaves, not
                     static, so hyperparameter sweeps over replace windows
                     reuse one compiled program.

    Static fields:
      kind             — 'replace' | 'refine' | 'none' (base transform).
      self_max_pixels  — inject only into maps this small: 16²=256 in
                         `/root/reference/main.py:170`, 32²=1024 in
                         `/root/reference/null_text.py:225` (intentional
                         behavioral difference between the two variants).
                         Static: it gates which layers get edit ops at all.
                         The factories leave a ``base.PaperLevel`` here when
                         nobody gave a bound; ``AttnLayout.resolve`` makes
                         it this model's number before anything is traced.
    """

    cross_alpha: jax.Array
    mapper: Optional[jax.Array] = None
    refine_alphas: Optional[jax.Array] = None
    equalizer: Optional[jax.Array] = None
    self_start: jax.Array = struct.field(default_factory=lambda: jnp.int32(0))
    self_end: jax.Array = struct.field(default_factory=lambda: jnp.int32(0))
    kind: str = struct.field(pytree_node=False, default="none")
    self_max_pixels: Union[int, "PaperLevel"] = struct.field(
        pytree_node=False, default=16 * 16)


def base_cross_transform(
    params: EditParams, attn_base: jax.Array, attn_edit: jax.Array
) -> jax.Array:
    """The kind-specific map from the source prompt's attention to candidate
    edit attention, before the time-schedule blend.

    attn_base: (H, P, L); attn_edit: (E, H, P, L); returns (E, H, P, L).
    """
    if params.kind == "replace":
        # Project source token columns through the (L, L) word-swap matrix:
        # the einsum of `/root/reference/main.py:218`.
        # HIGHEST precision: this projects probability mass; bf16 MXU default
        # would visibly perturb the attention rows it rewrites.
        return jnp.einsum("hpw,ewn->ehpn", attn_base, params.mapper,
                          precision=jax.lax.Precision.HIGHEST)
    if params.kind == "refine":
        # Gather source columns at mapper positions, blend by per-token
        # alphas (`/root/reference/main.py:236-238`). mapper entries of -1
        # (tokens new in the edit prompt) wrap to the last column but carry
        # alpha 0, so they fall through to the edit prompt's own attention.
        gathered = jnp.take(attn_base, params.mapper, axis=2)  # (H, P, E, L)
        gathered = jnp.moveaxis(gathered, 2, 0)                # (E, H, P, L)
        return gathered * params.refine_alphas + attn_edit * (1.0 - params.refine_alphas)
    if params.kind == "none":
        return jnp.broadcast_to(attn_base[None], attn_edit.shape)
    raise ValueError(f"unknown edit kind: {params.kind!r}")


def edit_cross_attention(
    params: EditParams, attn_base: jax.Array, attn_edit: jax.Array, step: jax.Array
) -> jax.Array:
    """Full cross-attention edit: base transform, optional equalizer scaling
    (Reweight, `/root/reference/main.py:262-263` — note the reference leaves
    rows unnormalized afterwards, `/root/reference/null_text.py:296,322`, and
    so do we), then the per-step/per-token schedule blend
    (`/root/reference/main.py:188-193`). Applies at every resolution — only
    self-attention is size-gated."""
    new = base_cross_transform(params, attn_base, attn_edit)
    if params.equalizer is not None:
        new = new * params.equalizer[:, None, None, :]
    alpha = jax.lax.dynamic_index_in_dim(params.cross_alpha, step, axis=0, keepdims=False)
    # alpha: (E, 1, 1, L) — broadcasts over (E, H, P, L).
    return new * alpha + (1.0 - alpha) * attn_edit


def edit_self_attention(
    params: EditParams,
    attn_base: jax.Array,
    attn_edit: jax.Array,
    step: jax.Array,
    pixels: int,
) -> jax.Array:
    """Self-attention injection: inside the ``[self_start, self_end)`` step
    window, maps with ≤ ``self_max_pixels`` query pixels are overwritten by
    the source prompt's maps (`/root/reference/main.py:169-174,183,195`).
    The size gate is static; the step window is a traced predicate."""
    if pixels > params.self_max_pixels:
        return attn_edit
    in_window = _in_self_window(params, step)
    injected = jnp.broadcast_to(attn_base[None], attn_edit.shape)
    return jnp.where(in_window, injected, attn_edit)


def inject_self_operands(
    params: EditParams,
    q: jax.Array,
    k: jax.Array,
    step: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The same injection on a self site's operands instead of its maps: an
    edit row's map inside the window is the base row's, ``softmax(q_base
    k_baseᵀ)``, so its output is that map times its own ``v``. Handing
    attention the base row's q and k in the edit rows computes exactly that
    with no map in memory. For a site within the size gate
    (``controllers.base.controller_only_injects``). q, k: ``(2B, heads, P,
    D)``, rows ``[:B]`` the unconditional half, row ``B`` the source prompt;
    returned as they came outside the window, or with no edit row (B = 1)."""
    b = q.shape[0] // 2
    if b <= 1:
        return q, k
    edit_row = (jnp.arange(q.shape[0]) > b)[:, None, None, None]
    take = jnp.logical_and(_in_self_window(params, step), edit_row)
    return jnp.where(take, q[b], q), jnp.where(take, k[b], k)


def _in_self_window(params: EditParams, step: jax.Array) -> jax.Array:
    """The self-injection window ``[self_start, self_end)``, traced."""
    return jnp.logical_and(step >= params.self_start, step < params.self_end)
