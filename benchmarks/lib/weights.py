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
key projections of the U-Net carry a gain above 1 (the configuration's
``assumed.attention_logit_gain``): with gain 1 every softmax over random
weights is flat, and an edit that swaps one flat attention map for another
would change nothing that the output check could see.
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
    if leaf == "pos_embed":
        return "normal", 0.5, 0.0
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


def _parts(stacks):
    """``(first, end)`` ranges of stacks, in order, each of at most
    ``FILL_BYTES`` of draws (or of one stack)."""
    parts, first, size = [], 0, 0
    for at, stack in enumerate(stacks):
        draws = _draw_bytes(stack[0], stack[-1])
        if size and size + draws > FILL_BYTES:
            parts.append((first, at))
            first, size = at, 0
        size += draws
    return parts + [(first, len(stacks))]


@partial(jax.jit, static_argnums=(1, 2))
def _fill(key, stacks, order):
    """The leaves ``order`` names, from the stacks it names. The key is split
    over all stacks whichever are drawn, so a leaf's values do not depend on
    how the stacks were divided into calls."""
    wanted = {at for at, _ in order}
    drawn = {}
    for at, (k, (shape, dtype, kind, spread, offset, count)) in enumerate(zip(
            jax.random.split(key, len(stacks)), stacks)):
        if at not in wanted:
            continue
        if kind == "uniform":
            x = jax.random.uniform(k, (count,) + shape, jnp.float32, -1.0, 1.0)
        else:
            x = jax.random.normal(k, (count,) + shape, jnp.float32)
        drawn[at] = (x * spread + offset).astype(dtype)
    return [jax.lax.index_in_dim(drawn[at], i, 0, keepdims=False)
            for at, i in order]


def make_weights(seed: int, shapes, qk_gain: float):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct`` (dicts and lists).
    Returns the same tree filled from ``seed``, each leaf in its own type."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    spec = []
    for keypath, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in keypath)
        spec.append((tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
                    + _rule(path, leaf.shape, qk_gain))
    # XLA's own bit generator: a threefry per draw is unrolled into the
    # program and took a minute to compile.
    key = jax.random.key(seed % (2 ** 63), impl="rbg")
    stacks, order = _stacks(spec)
    leaves = [None] * len(order)
    for first, end in _parts(stacks):
        mine = [i for i, (at, _) in enumerate(order) if first <= at < end]
        for i, leaf in zip(mine, _fill(key, stacks, tuple(order[i] for i in mine))):
            leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)
