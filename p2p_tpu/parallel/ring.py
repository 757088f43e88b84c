"""Ring attention: sequence-parallel self-attention over latent pixels.

The reference caps at 64×64 latents where full (S, S) attention fits on one
device; its analogous scaling axis is image resolution — self-attention is
quadratic in latent pixels (SURVEY §5: `show_self_attention_comp` builds the
full (res², res²) matrix, `/root/reference/main.py:336-337`). For
high-resolution editing the pixel axis must shard across devices.

This module implements blockwise ring attention (Liu et al., arXiv
2310.01889) TPU-natively: each device holds an S/n shard of q/k/v; k/v shards
rotate around the mesh axis via `jax.lax.ppermute` (ICI neighbor exchange, no
all-gather), while a numerically-stable online softmax accumulates partial
results — flash attention's (m, l, acc) recurrence, distributed.

Communication: n-1 ppermute rounds of the local (B, H, S/n, D) k/v shards —
bandwidth S·D per device total, independent of the O(S²) score matrix that
never materializes anywhere.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attend_einsum(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    m = s.max(axis=-1)                                   # (B, H, Sq)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _block_attend_flash(q, k, v, scale):
    """Flash-kernel block (residuals variant): the local (Sq, Sk) scores
    never materialize, so per-shard HBM stays O(S_local·D) however long the
    local chunk. The kernel's save_residuals mode has no VJP of its own —
    the custom rule below recomputes the block through the einsum
    formulation, so callers that differentiate the ring (e.g. a
    sequence-parallel null-text inversion) keep working at einsum cost
    while forward-only sampling gets the kernel."""
    from ..models import nn

    o, l, m = nn.flash_attention_residuals(
        q, k, v, scale,
        nn.flash_block(q.shape[-2], q.shape[-1], q.dtype.itemsize))
    # The kernel returns the *normalized* local output; the ring merge
    # needs the unnormalized accumulator acc = o·l.
    return o.astype(jnp.float32) * l[..., None].astype(jnp.float32), m, l


def _block_attend_flash_fwd(q, k, v, scale):
    return _block_attend_flash(q, k, v, scale), (q, k, v)


def _block_attend_flash_bwd(scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: _block_attend_einsum(q, k, v, scale),
                     q, k, v)
    return vjp(g)


_block_attend_flash.defvjp(_block_attend_flash_fwd, _block_attend_flash_bwd)


def _block_attend(q, k, v, scale, use_flash=False):
    """Unnormalized flash-style block: returns (acc, m, l) for one k/v block.

    q: (B, H, Sq, D); k,v: (B, H, Sk, D) →
    acc (B, H, Sq, D) f32, m/l (B, H, Sq) f32.

    ``use_flash`` routes the block through the Pallas kernel when the chunk
    tiles it; non-tileable shapes (and the CPU tests) take the einsum path.
    """
    from ..models import nn

    if (use_flash and q.shape[-2] == k.shape[-2]
            and nn.flash_block(q.shape[-2], q.shape[-1],
                               q.dtype.itemsize) is not None):
        return _block_attend_flash(q, k, v, scale)
    return _block_attend_einsum(q, k, v, scale)


def _merge(acc1, m1, l1, acc2, m2, l2):
    """Combine two partial softmax accumulations (log-sum-exp merge)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def ring_self_attention_shard(
    q: jax.Array, k: jax.Array, v: jax.Array, scale: float, axis_name: str,
    use_flash: bool = False,
) -> jax.Array:
    """Per-shard body (call inside `shard_map`): q/k/v are the local
    (B, H, S_local, D) shards; the sequence axis is sharded over
    ``axis_name``. Returns the local output shard."""
    n = jax.lax.psum(1, axis_name)

    acc, m, l = _block_attend(q, k, v, scale, use_flash)

    def round_body(i, carry):
        acc, m, l, k, v = carry
        # Rotate k/v one step around the ring (neighbor ICI exchange).
        perm = [(j, (j + 1) % n) for j in range(n)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        acc2, m2, l2 = _block_attend(q, k, v, scale, use_flash)
        acc, m, l = _merge(acc, m, l, acc2, m2, l2)
        return acc, m, l, k, v

    acc, m, l, _, _ = jax.lax.fori_loop(0, n - 1, round_body, (acc, m, l, k, v))
    return (acc / l[..., None]).astype(q.dtype)


def ring_self_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
    mesh: Mesh, axis_name: str = "sp",
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Sequence-parallel self-attention entry point.

    q,k,v: (B, H, S, D) with S divisible by the mesh axis size. The arrays are
    sharded over ``axis_name`` on their S dimension, attended with ring
    communication, and returned with the same sharding.

    ``use_flash``: run each local block through the Pallas flash kernel so
    per-shard HBM stays O(S_local·D). Default (None): where
    ``nn.fused_attention`` would take the kernel for the local chunk.
    """
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(f"sequence length {q.shape[2]} not divisible by "
                         f"{axis_name}={n}")
    if use_flash is None:
        from ..models import nn

        use_flash = nn.takes_flash_kernel(
            q.shape[2] // n, q.shape[-1], q.dtype.itemsize)
    spec = P(None, None, axis_name, None)
    # check_vma only off for the flash chunks: pallas_call does not yet carry
    # the varying-mesh-axes metadata shard_map's checker wants. The einsum
    # path keeps the checker on.
    f = jax.shard_map(
        partial(ring_self_attention_shard, scale=scale, axis_name=axis_name,
                use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not use_flash)
    return f(q, k, v)


def sp_sharding(mesh: Mesh, axis_name: str = "sp") -> NamedSharding:
    """Sharding for (B, H, S, D) tensors with the pixel/sequence axis
    distributed."""
    return NamedSharding(mesh, P(None, None, axis_name, None))
