"""The fused-edit attention kernel: softmax + prompt-to-prompt edit, tiled.

One Pallas program instance owns one ``(block_q, D)`` query tile of one
``(batch row, head)`` and computes, entirely in VMEM:

    logits = q·kᵀ·scale + pad_mask          (block_q, Kp)   f32
    probs  = softmax(logits)                 rows are FULL — K is the cross
                                             context length (77 → 128 padded)
                                             or an edited self site's pixels
                                             (≤ 1024), so no online-softmax
                                             streaming is needed
    base   = softmax(q_base·k_baseᵀ·scale)   the source prompt's row, computed
                                             in-tile from its own q/k blocks
                                             (edit rows depend on the base row;
                                             recomputing its tile keeps the
                                             kernel free of cross-instance
                                             communication)
    edited = blend(edit(base, probs))        the controllers.kernel_spec
                                             operand algebra — Replace/Refine
                                             as a (Kp, Kp) in-tile matmul,
                                             Reweight as a key-token scale,
                                             self-injection as an α ∈ {0,1}
                                             blend
    out    = rowselect(edited | probs) @ v   (block_q, D)

The ``(2B·heads, P, K)`` probability tensor therefore never exists outside a
VMEM tile: the kernel's only HBM traffic is q/k/v in and the attention
output out — the same footprint as flash attention. Edit rows are the CFG
batch's conditional rows ``b+1 … 2b−1``; uncond rows and the base row take
the plain-softmax path through the identical program (the edit algebra is
computed and discarded — cheap at these K, and it keeps the grid uniform).

Numerics: all probability math in f32, the Replace/Refine projection at
``Precision.HIGHEST`` — matching the materialized reference path
(``models/nn.py:attention_probs`` + ``controllers.base``). Non-edited rows
are exactly a (blockwise) softmax-attention; edited rows carry the
documented 1e-2 golden drift budget vs the reference (tiling changes
reduction order). Interpret mode (`.interpret`) runs the identical program
on CPU — the rehearsal surface every parity test pins.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import jax.experimental.pallas as pl

from ..controllers.base import apply_attention_control
from ..controllers.kernel_spec import EditSpec, edit_operands, kernel_edit_spec
from ..models import nn

# Additive mask value for lane-padded key columns — the library flash
# kernel's DEFAULT_MASK_VALUE, so padded columns underflow to exactly the
# same zero probability there and here.
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def pad_to_lanes(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to ``target`` (a lane multiple)."""
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _softmax_rows(logits: jax.Array) -> jax.Array:
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _edit_kernel(*refs, spec: EditSpec, scale: float, b_half: int,
                 num_edits: int):
    """Kernel body. ``refs`` order (built by :func:`edit_attention`):
    q, q_base, k, k_base, v, kmask, [transform], [refine_mix],
    [equalizer], blend, out."""
    it = iter(refs)
    q_ref, qb_ref, k_ref, kb_ref, v_ref, kmask_ref = (next(it) for _ in range(6))
    t_ref = next(it) if spec.has_transform else None
    ra_ref = next(it) if spec.kind == "refine" else None
    eq_ref = next(it) if spec.has_equalizer else None
    alpha_ref = next(it)
    o_ref = next(it)

    mask = kmask_ref[0][None, :]                               # (1, Kp)

    def probs_of(qr, kr):
        qt = qr[0, 0].astype(jnp.float32)                      # (bq, D)
        kt = kr[0, 0].astype(jnp.float32)                      # (Kp, D)
        logits = jax.lax.dot_general(
            qt, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + mask
        return _softmax_rows(logits)                           # (bq, Kp)

    probs = probs_of(q_ref, k_ref)
    base = probs_of(qb_ref, kb_ref)

    # The controllers.kernel_spec row-local edit algebra.
    if spec.has_transform:
        new = jax.lax.dot_general(
            base, t_ref[0], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    else:
        new = base
    if ra_ref is not None:
        ra = ra_ref[0][None, :]
        new = new * ra + probs * (1.0 - ra)
    if eq_ref is not None:
        new = new * eq_ref[0][None, :]
    alpha = alpha_ref[0][None, :]
    edited = new * alpha + (1.0 - alpha) * probs

    is_edit_row = pl.program_id(0) >= b_half + 1
    probs_out = jnp.where(is_edit_row, edited, probs)

    vt = v_ref[0, 0]                                           # (Kp, D)
    out = jax.lax.dot_general(
        probs_out.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def edit_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                   spec: EditSpec, operands: dict, *,
                   block_q: int = 0, interpret: bool = False) -> jax.Array:
    """Fused attention with the in-kernel prompt-to-prompt edit.

    q, k, v: ``(2B, heads, P, K|D)`` — the CFG-doubled batch
    ``[uncond(B); base; edits(E)]``; ``operands`` from
    :func:`controllers.kernel_spec.edit_operands` (already indexed at the
    step). Returns ``(2B, heads, P, D)`` in ``v.dtype``. ``block_q=0``
    picks the largest VMEM-feasible query block (``models.nn.edit_block``);
    ``interpret=True`` runs the pallas interpreter (the CPU parity
    surface)."""
    two_b, heads, pixels, d_head = q.shape
    b_half = two_b // 2
    num_edits = b_half - 1
    if num_edits < 1:
        raise ValueError(
            f"fused edit kernel needs a base row + ≥1 edit row in the cond "
            f"half, got CFG batch {two_b} (b={b_half})")
    kp = spec.pad_len
    assert k.shape[2] == spec.key_len, (k.shape, spec)
    if not block_q:
        block_q = nn.edit_block(pixels, spec.key_len, d_head,
                                jnp.dtype(q.dtype).itemsize)
    if not block_q or pixels % block_q:
        raise ValueError(
            f"no VMEM-feasible query block for P={pixels}, K={spec.key_len}, "
            f"D={d_head} (got block_q={block_q})")
    k_p = pad_to_lanes(k, 2, kp)
    v_p = pad_to_lanes(v, 2, kp)
    kmask = jnp.where(jnp.arange(kp) < spec.key_len, 0.0,
                      _MASK_VALUE).astype(jnp.float32)[None, :]    # (1, Kp)

    def qmap(b, h, i):
        return (b, h, i, 0)

    def qmap_base(b, h, i):
        return (b_half, h, i, 0)

    def kmap(b, h, i):
        return (b, h, 0, 0)

    def kmap_base(b, h, i):
        return (b_half, h, 0, 0)

    def rowmap(b, h, i):
        # Edit-operand row for this batch row; non-edit rows clamp to row 0
        # (their edit result is computed and discarded).
        return (jnp.clip(b - b_half - 1, 0, num_edits - 1), 0)

    def rowmap3(b, h, i):
        return (jnp.clip(b - b_half - 1, 0, num_edits - 1), 0, 0)

    q_spec = pl.BlockSpec((1, 1, block_q, d_head), qmap)
    qb_spec = pl.BlockSpec((1, 1, block_q, d_head), qmap_base)
    k_spec = pl.BlockSpec((1, 1, kp, d_head), kmap)
    kb_spec = pl.BlockSpec((1, 1, kp, d_head), kmap_base)

    inputs = [q, q, k_p, k_p, v_p, kmask]
    in_specs = [q_spec, qb_spec, k_spec, kb_spec, k_spec,
                pl.BlockSpec((1, kp), lambda b, h, i: (0, 0))]
    if spec.has_transform:
        inputs.append(operands["transform"])
        in_specs.append(pl.BlockSpec((1, kp, kp), rowmap3))
    if spec.kind == "refine":
        inputs.append(operands["refine_mix"])
        in_specs.append(pl.BlockSpec((1, kp), rowmap))
    if spec.has_equalizer:
        inputs.append(operands["equalizer"])
        in_specs.append(pl.BlockSpec((1, kp), rowmap))
    inputs.append(operands["blend"])
    in_specs.append(pl.BlockSpec((1, kp), rowmap))

    kernel = functools.partial(_edit_kernel, spec=spec, scale=scale,
                               b_half=b_half, num_edits=num_edits)
    call = pl.pallas_call(
        kernel,
        grid=(two_b, heads, pixels // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d_head), qmap),
        out_shape=jax.ShapeDtypeStruct((two_b, heads, pixels, d_head),
                                       v.dtype),
        interpret=interpret,
    )
    return nn.per_device(call)(*inputs)


def edit_attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                             scale: float, controller, meta,
                             step: jax.Array) -> jax.Array:
    """The materialized reference path for one site, exactly as
    ``models/unet.py`` runs it when the kernel is off: f32 probabilities
    through ``apply_attention_control``, then ``probs @ v``. The parity
    harness ground truth (store-free sites only — which is all the kernel
    dispatches to)."""
    probs = nn.attention_probs(q, k, scale)
    _, probs = apply_attention_control(controller, meta, (), probs, step)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def fused_site_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         scale: float, controller, meta, step: jax.Array, *,
                         block_q: int = 0,
                         interpret: bool = False) -> Optional[jax.Array]:
    """Site-level entry: extract the spec from the controller treedef, build
    the step's operands, run the kernel. ``None`` when the site is not
    kernel-compilable (caller falls back to the materialized path) — also
    when the batch has no edit rows, which only trace-time shapes reveal."""
    spec = kernel_edit_spec(controller, meta)
    if spec is None or q.shape[0] // 2 < 2:
        return None
    if not block_q:
        block_q = nn.edit_block(q.shape[2], spec.key_len, q.shape[3],
                                jnp.dtype(q.dtype).itemsize)
    if not block_q or q.shape[2] % block_q:
        return None
    ops = edit_operands(controller.edit, spec, step)
    return edit_attention(q, k, v, scale, spec, ops, block_q=block_q,
                          interpret=interpret)
