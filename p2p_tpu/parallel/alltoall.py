"""All-to-all (Ulysses-style) sequence-parallel self-attention.

The second canonical sequence-parallel scheme next to ring attention
(DeepSpeed-Ulysses, arXiv 2309.14509): instead of rotating k/v shards
around the mesh (n−1 ppermute rounds), ONE all-to-all redistributes the
pixel-sharded (B, H, S/n, D) q/k/v into head-sharded (B, H/n, S, D)
tensors, each device runs ordinary full-sequence attention for its head
subset (the Pallas flash kernel on TPU), and a second all-to-all restores
the pixel sharding.

Trade-off vs ring: two all-to-alls of the q/k/v/o tensors (4·S·D per
device) against n−1 neighbor exchanges of k/v (2·S·D), but the attention
itself is a single dense local call — no per-round merge arithmetic, and
the full-row softmax is exact without the online-merge recurrence. It
requires heads % n == 0, which the integration layer checks — sites with
indivisible head counts take the ring (always valid on the pixel axis).
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P


def alltoall_self_attention_shard(
    q: jax.Array, k: jax.Array, v: jax.Array, scale: float, axis_name: str,
) -> jax.Array:
    """Per-shard body (inside `shard_map`): q/k/v are local
    (B, H, S_local, D) shards, sequence axis sharded over ``axis_name``;
    returns the local output shard."""
    from ..models import nn

    def to_heads(t):   # (B, H, S/n, D) → (B, H/n, S, D)
        return jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def to_pixels(t):  # (B, H/n, S, D) → (B, H, S/n, D)
        return jax.lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    out = nn.fused_attention(to_heads(q), to_heads(k), to_heads(v), scale)
    return to_pixels(out)


def alltoall_self_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
    mesh: Mesh, axis_name: str = "sp",
) -> jax.Array:
    """Sequence-parallel self-attention via head redistribution.

    q,k,v: (B, H, S, D) with S divisible by the mesh axis size AND
    H divisible by it (each device attends a head subset over the full
    sequence). Arrays are sharded over ``axis_name`` on S, redistributed,
    attended, and returned with the same S sharding."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(f"sequence length {q.shape[2]} not divisible by "
                         f"{axis_name}={n}")
    if q.shape[1] % n:
        raise ValueError(f"head count {q.shape[1]} not divisible by "
                         f"{axis_name}={n} (use ring attention for this "
                         f"site, or shrink the sp axis)")
    spec = P(None, None, axis_name, None)
    # check_vma off for the same reason as the ring's flash chunks: the
    # local attention may lower to pallas_call, which doesn't yet carry
    # the varying-mesh-axes metadata shard_map's checker wants.
    from ..models import nn

    f = jax.shard_map(
        partial(alltoall_self_attention_shard, scale=scale,
                axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not nn._on_tpu())
    return f(q, k, v)
