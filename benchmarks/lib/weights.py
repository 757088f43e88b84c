"""Weights from ``--seed``: made on the device, in jitted calls, in the
type they are served in.

The tree's names, shapes and types are those of the program's own
initialisers (read with ``jax.eval_shape``, so nothing of the program's is
computed); the values are the benchmark's. Every value is drawn in float32
and cast inside the jitted fill to the type the initialiser gives its leaf,
a stack at a time, so a leaf the program stores narrower never exists wider
than one stack. The program is handed the tree as its ``Pipeline``
parameters, the plain reference reads the same tree by name.

Kernels are uniform in +-gain / sqrt(fan_in), the scale the checkpoints'
framework initialises with. Biases and norm offsets are small and not zero,
and norm scales are not one, so that leaving one out shows. The query and
key projections of the denoiser (``to_q``, ``to_k``: a U-Net's or a
transformer's) carry a gain above 1 (the configuration's
``assumed.attention_logit_gain``): with gain 1 every softmax over random
weights is flat, and an edit that swaps one flat attention map for another
would change nothing that the output check could see. A transformer's
adaLN-single tables (a leaf name ending in ``table``: its ``(6, width)``
shift, scale and gate rows) are normal over sqrt(width), as diffusers draws
them; a T5 tower's ``relative_attention_bias`` (buckets by heads, added to
the logits) is normal at the spread of the learned positions.

The fill is bounded by the chip, not by the tree: a stack whose float32
draws come to more than ``SLICE_BYTES`` is drawn in slices along its leading
axis, and a call draws at most what fits beside the whole tree
(``call_budget``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def _rule(path: str, shape, qk_gain: float):
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "kernel":
        gain = qk_gain if ("/to_q/" in path or "/to_k/" in path) else 1.0
        return "uniform", gain / math.sqrt(math.prod(shape[:-1])), 0.0
    if leaf == "scale":
        return "uniform", 0.1, 1.0
    if leaf == "bias":
        return "uniform", 0.05, 0.0
    if leaf == "token_embed":
        return "normal", 1.0, 0.0
    if leaf in ("pos_embed", "relative_attention_bias"):
        return "normal", 0.5, 0.0
    if leaf.endswith("table"):
        return "normal", 1.0 / math.sqrt(shape[-1]), 0.0
    if leaf == "codebook":
        return "normal", 1.0, 0.0
    raise ValueError(f"no initialisation rule for leaf {path!r}")


#: Leaves of one shape, type and rule are drawn as one stacked array of at
#: most this many bytes and sliced apart: a draw per leaf made the ~1100
#: leaves some 20,000 traced operations, seconds of every run's set-up. The
#: cap keeps the stack, which lives beside its slices for a moment, far under
#: the window's own peak.
STACK_BYTES = 32 << 20

#: A tree whose float32 draws come to more than this is filled in several
#: calls, so that the peak of the fill stays near the tree's own size: the
#: outputs of one call are all live at once, and beside them whatever of its
#: draws XLA keeps. Both cells' trees (3.97 and 4.80 GiB) stay one call.
FILL_BYTES = 6 << 30

#: A stack whose float32 draws come to more than this (one leaf larger than
#: ``STACK_BYTES``, such as a tower's layers stacked for a scan) is drawn in
#: slices of its leading axis, each at most this large, cast and joined. No
#: stack of the three cells' trees comes near it (the largest is one token
#: table, 253 MB of draws), so theirs are drawn whole, as before.
SLICE_BYTES = 1 << 30

#: Room kept free beside the tree and one call's draws: the programs' code
#: and the allocator's own.
HEADROOM_BYTES = 1 << 30


def _draw_bytes(shape, count: int = 1) -> int:
    return 4 * count * max(1, math.prod(shape))


def _stacks(spec):
    """``spec`` rows ``(shape, dtype, kind, spread, offset)`` -> ``(stacks,
    order)``: stacks ``(shape, dtype, kind, spread, offset, count)`` and, for
    each row of ``spec``, its ``(stack, position)``."""
    room = {}
    stacks, order = [], []
    for row in spec:
        most = max(1, STACK_BYTES // _draw_bytes(row[0]))
        at = room.get(row)
        if at is None or stacks[at][-1] >= most:
            at = room[row] = len(stacks)
            stacks.append([*row, 0])
        order.append((at, stacks[at][-1]))
        stacks[at][-1] += 1
    return tuple(tuple(s) for s in stacks), tuple(order)


def _parts(stacks, budget=None):
    """``(first, end)`` ranges of stacks, in order, each of at most
    ``budget`` (``FILL_BYTES`` unless given) of draws (or of one stack)."""
    budget = FILL_BYTES if budget is None else budget
    parts, first, size = [], 0, 0
    for at, stack in enumerate(stacks):
        draws = _draw_bytes(stack[0], stack[-1])
        if size and size + draws > budget:
            parts.append((first, at))
            first, size = at, 0
        size += draws
    return parts + [(first, len(stacks))]


def tree_bytes(shapes) -> int:
    """Bytes of the filled tree, each leaf in its own type."""
    return sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(shapes))


def call_budget(tree: int, chip) -> int:
    """Draws one call may make: ``FILL_BYTES``, or less where the chip's
    ``chip`` bytes hold no more beside the ``tree`` bytes of every output and
    ``HEADROOM_BYTES``: the fill's peak is at most the tree and one call's
    draws. ``chip`` None (a backend that reports no limit) leaves
    ``FILL_BYTES``. A tree that leaves no room for one slice is refused."""
    if chip is None:
        return FILL_BYTES
    room = chip - tree - HEADROOM_BYTES
    if room < SLICE_BYTES:
        raise ValueError(f"a tree of {tree} B leaves {room} B of a {chip} B "
                         f"chip for the fill's draws, under one slice of "
                         f"{SLICE_BYTES} B")
    return min(FILL_BYTES, room)


def plan(shapes, qk_gain: float, chip=None):
    """``(stacks, order, parts)`` of the fill of ``shapes``: what
    ``make_weights`` draws, how the leaves are cut from it, and the calls."""
    stacks, order = _stacks(_spec(shapes, qk_gain))
    return stacks, order, _parts(stacks, call_budget(tree_bytes(shapes), chip))


def _slices(stack):
    """``(axis, sizes)`` of the slices a stack is drawn in, along its
    leading axis (the leaf's, where the stack holds one leaf); None where its
    draws are within ``SLICE_BYTES`` and it is drawn whole."""
    shape, count = stack[0], stack[-1]
    if _draw_bytes(shape, count) <= SLICE_BYTES:
        return None
    full = (count,) + shape
    axis = 1 if count == 1 and shape else 0
    rows = max(1, SLICE_BYTES // _draw_bytes(full[axis + 1:]))
    return axis, [min(rows, full[axis] - i) for i in range(0, full[axis], rows)]


def _draw(key, stack):
    """One stack's values in its type: whole, or in its ``_slices`` from keys
    split off ``key``."""
    shape, dtype, kind, spread, offset, count = stack
    full = (count,) + shape

    def draw(k, dims):
        if kind == "uniform":
            x = jax.random.uniform(k, dims, jnp.float32, -1.0, 1.0)
        else:
            x = jax.random.normal(k, dims, jnp.float32)
        return (x * spread + offset).astype(dtype)

    sliced = _slices(stack)
    if sliced is None:
        return draw(key, full)
    axis, sizes = sliced
    keys = jax.random.split(key, len(sizes))
    return jnp.concatenate(
        [draw(k, full[:axis] + (n,) + full[axis + 1:]) for k, n in zip(keys, sizes)],
        axis=axis)


@partial(jax.jit, static_argnums=(1, 2))
def _fill(key, stacks, order):
    """The leaves ``order`` names, from the stacks it names. The key is split
    over all stacks whichever are drawn, so a leaf's values do not depend on
    how the stacks were divided into calls."""
    wanted = {at for at, _ in order}
    drawn = {at: _draw(k, stack) for at, (k, stack) in enumerate(zip(
        jax.random.split(key, len(stacks)), stacks)) if at in wanted}
    return [jax.lax.index_in_dim(drawn[at], i, 0, keepdims=False)
            for at, i in order]


def _spec(shapes, qk_gain: float):
    """Rows ``(shape, dtype, kind, spread, offset)``, one a leaf in order."""
    spec = []
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in keypath)
        spec.append((tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
                    + _rule(path, leaf.shape, qk_gain))
    return spec


def chip_bytes():
    """The first device's memory limit; None where it reports none."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def make_weights(seed: int, shapes, qk_gain: float):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct`` (dicts and lists).
    Returns the same tree filled from ``seed``, each leaf in its own type."""
    treedef = jax.tree.structure(shapes)
    # XLA's own bit generator: a threefry per draw is unrolled into the
    # program and took a minute to compile.
    key = jax.random.key(seed % (2 ** 63), impl="rbg")
    stacks, order, parts = plan(shapes, qk_gain, chip_bytes())
    leaves = [None] * len(order)
    for first, end in parts:
        mine = [i for i, (at, _) in enumerate(order) if first <= at < end]
        for i, leaf in zip(mine, _fill(key, stacks, tuple(order[i] for i in mine))):
            leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)
