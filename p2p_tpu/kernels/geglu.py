"""The GEGLU feed-forward of a transformer block in one Pallas kernel.

``x + ff_out(val · gelu(gate))``, where ``val ‖ gate = ff_in(ln3(x))``: the
kernel takes the layer-normed rows and the residual, and for each tile of
rows walks the inner width in chunks. For a chunk it multiplies the rows by
the chunk's value and gate columns of ``ff_in`` (two products on one input
tile), applies the exact GELU and the product in f32 in VMEM, and adds the
chunk's ``ff_out`` product into the f32 output tile, which stays in VMEM
across the walk. Both biases and the residual join at the ends. So the
``(rows, 2·inner)`` f32 product of ``ff_in``, and the ``(rows, inner)`` one
``ff_out`` reads, never reach HBM (XLA's two fusions write and read them
back: PERF.md §6).

Widths: the normed rows and the weights are handed over in bfloat16, the
width the MXU multiplies f32 operands in at the default precision
(``nn.flash_operand_dtype``), and so is the chunk's product on its way into
``ff_out``; accumulation, biases, GELU and the residual stay f32. The math is
the formula's (:func:`feed_forward_formula`): only the order of the sums in
``ff_out`` differs. The gradient is the formula's too: ``custom_vjp``
recomputes it from the saved inputs (null-text inversion differentiates
through every block). Under ``vmap`` the call batches over a grid axis.

Where the model runs it: :func:`plan`, from the shape, the platform and the
mesh (``models/unet.py``'s ``ff`` scope).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models import nn

# erf(x) = x·P(x²)/Q(x²) on x clamped to ±erfinv(1 − 2⁻²³), beyond which it
# is ±1 in f32: the rational form XLA evaluates erf in for f32 (Mosaic has no
# erf of its own). Absolute error under 4e-7 against the f64 erf.
_ERF_ALPHA = (0.00022905065861350646, 0.0034082910107109506,
              0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_BETA = (-1.1791602954361697e-7, 0.000023547966471313185,
             0.0010179625278914885, 0.014070470171167667,
             0.11098505178285362, 0.49746925110067538, 1.0)
_ERF_CLAMP = 3.7439211627767994


def _polynomial(coefficients, x):
    out = jnp.full_like(x, coefficients[0])
    for c in coefficients[1:]:
        out = out * x + c
    return out


def _gelu(x):
    """The exact GELU, ``x·Φ(x)``, as ``nn.gelu`` (no tanh form)."""
    z = jnp.clip(x * np.float32(np.sqrt(0.5)), -_ERF_CLAMP, _ERF_CLAMP)
    z2 = z * z
    erf = z * _polynomial(_ERF_ALPHA, z2) / _polynomial(_ERF_BETA, z2)
    return 0.5 * x * (1.0 + erf)


def feed_forward_formula(x, normed, p_in, p_out):
    """The block's feed-forward as XLA runs it: ``x + ff_out(val ·
    gelu(gate))`` on ``ff_in(normed)``, every array in ``x``'s dtype."""
    h = nn.linear(p_in, normed)
    val, gate = jnp.split(h, 2, axis=-1)
    return x + nn.linear(p_out, val * nn.gelu(gate))


def _ff_kernel(h_ref, wv_ref, wg_ref, bv_ref, bg_ref, wo_ref, bo_ref, x_ref,
               o_ref):
    """One (row tile, inner chunk) step; the output tile is the accumulator
    (its block does not move along the chunk axis, so it stays in VMEM)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = h_ref[...]
    val = jnp.dot(h, wv_ref[...], preferred_element_type=jnp.float32) + bv_ref[...]
    gate = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32) + bg_ref[...]
    y = (val * _gelu(gate)).astype(wo_ref.dtype)
    o_ref[...] += jnp.dot(y, wo_ref[...], preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = x_ref[...] + (o_ref[...] + bo_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pallas(normed, w_in, b_in, w_out, b_out, x, *, tile, interpret):
    """The kernel on ``(rows, C)`` arrays: ``x`` f32, the rest as handed.
    Jitted, so the blocks of one shape share one trace and one lowering (a
    program of seventy blocks lowers the kernel once a shape, not seventy
    times)."""
    rows, channels = x.shape
    inner = w_out.shape[0]
    block_rows, chunk = tile
    chunks = inner // chunk
    rows_spec = pl.BlockSpec((block_rows, channels), lambda i, j: (i, 0))
    return pl.pallas_call(
        _ff_kernel,
        grid=(rows // block_rows, chunks),
        in_specs=[
            rows_spec,                                                # normed
            pl.BlockSpec((channels, chunk), lambda i, j: (0, j)),     # value
            pl.BlockSpec((channels, chunk), lambda i, j: (0, j + chunks)),
            pl.BlockSpec((1, chunk), lambda i, j: (0, j)),            # biases
            pl.BlockSpec((1, chunk), lambda i, j: (0, j + chunks)),
            pl.BlockSpec((chunk, channels), lambda i, j: (j, 0)),     # ff_out
            pl.BlockSpec((1, channels), lambda i, j: (0, 0)),
            rows_spec,                                                # residual
        ],
        out_specs=rows_spec,
        out_shape=jax.ShapeDtypeStruct((rows, channels), jnp.float32),
        # No dimension semantics: the v5e has one core to give a parallel
        # axis to, and the interpreter does not extend them over the grid
        # axis ``vmap`` prepends.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=nn._FF_VMEM_LIMIT),
        name="geglu_feed_forward",
        interpret=interpret,
    )(normed, w_in, w_in, b_in, b_in, w_out, b_out, x)


def _kernel_form(x, normed, p_in, p_out, tile, interpret):
    operand = jnp.bfloat16
    shape = x.shape
    flat = (-1, shape[-1])
    kernel = functools.partial(_pallas, tile=tile, interpret=interpret)
    out = nn.per_device(kernel)(
        normed.reshape(flat).astype(operand), p_in["kernel"].astype(operand),
        p_in["bias"].astype(jnp.float32)[None], p_out["kernel"].astype(operand),
        p_out["bias"].astype(jnp.float32)[None], x.reshape(flat))
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def geglu_feed_forward(x, normed, p_in, p_out, tile: Tuple[int, int],
                       interpret: bool = False):
    """``feed_forward_formula(x, normed, p_in, p_out)`` in one kernel call
    tiled by ``tile`` (an ``nn.ff_block`` answer): ``x`` and ``normed`` f32
    ``(..., C)``, ``p_in`` / ``p_out`` the ``ff_in`` / ``ff_out`` linears
    (kernels in any width, biases f32). ``interpret=True`` runs the Pallas
    interpreter (the CPU's way to execute it)."""
    return _kernel_form(x, normed, p_in, p_out, tile, interpret)


def _forward(x, normed, p_in, p_out, tile, interpret):
    out = _kernel_form(x, normed, p_in, p_out, tile, interpret)
    return out, (x, normed, p_in, p_out)


def _backward(tile, interpret, saved, g):
    _, vjp = jax.vjp(feed_forward_formula, *saved)
    return vjp(g)


geglu_feed_forward.defvjp(_forward, _backward)


def _partitioned(*arrays) -> bool:
    """Whether the program lays any of ``arrays`` over several devices in a
    way the kernel cannot follow: a Mosaic call is never partitioned
    automatically, and only a ``dp`` mesh under ``nn.kernel_mesh`` runs it
    per device (``tp`` shards ``ff_in``'s columns and ``ff_out``'s rows)."""
    for a in arrays:
        mesh = getattr(jax.typeof(a).sharding, "mesh", None)
        wide = {axis for axis, n in dict(getattr(mesh, "shape", {})).items()
                if n > 1}
        if wide and (nn._KERNEL_MESH is None or wide - {"dp"}):
            return True
    return False


def plan(x, p_in, p_out) -> Tuple[str, Optional[Tuple[int, int]]]:
    """How a block's feed-forward on ``x`` ``(..., C)`` runs: ``("kernel",
    tile)``; ``("sharded", None)`` on a mesh the kernel cannot follow;
    ``("formula", None)`` off TPU, for arrays that are not f32 at the
    default matmul precision (their products are not bfloat16 ones), and
    where ``nn.ff_block`` has no tile for one image's block (``x`` is
    ``(..., pixels, C)``) or its row tile does not divide the rows."""
    if _partitioned(p_in["kernel"], p_out["kernel"], x):
        return "sharded", None
    if not (nn._on_tpu() and x.dtype == jnp.float32
            and nn.flash_operand_dtype(x.dtype) == jnp.bfloat16):
        return "formula", None
    tile = nn.ff_block(x.shape[-2], x.shape[-1], p_out["kernel"].shape[0], 2)
    if tile is None or (x.size // x.shape[-1]) % tile[0]:
        return "formula", None
    return "kernel", tile
