"""Pallas flash-attention kernel parity, interpret mode (CPU).

The big self-attention sites (64² pixels → S=4096) run the Pallas TPU flash
kernel via `nn.flash_attention_tpu` (`p2p_tpu/models/nn.py`) — a path the CPU
test suite otherwise never executes. `force_tpu_interpret_mode()` executes the
*identical* kernel — same BlockSizes, same grid — in the Pallas interpreter
on CPU, so parity against the materialized `attention_probs` + einsum
reference is checked in CI.

Shapes mirror the production site: S=4096 (64² pixels), head_dim 40
(SD-1.4's 320/8), block 1024 (what `flash_block(4096)` picks). Batch and
heads are reduced (the kernel grid iterates them independently; geometry per
batch·head is what the blocks tile).

Tolerance: the kernel accumulates softmax/matmul in f32 like the reference
path, but blockwise online-softmax reassociates the sums — f32 inputs agree
to ~1e-5; bf16 inputs (the TPU production dtype) to a few 1e-2 in absolute
terms on O(1)-scale outputs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental.pallas.tpu import force_tpu_interpret_mode
from p2p_tpu.models import nn


def _ref(q, k, v, scale):
    probs = nn.attention_probs(q, k, scale).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _rand_qkv(seed, b, h, s, d, dtype):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d), dtype=dtype)
    return mk(), mk(), mk()


@pytest.mark.slow
def test_flash_interpret_parity_f32_sd_shape():
    s, d = 4096, 40  # the 64²-pixel SD-1.4 site
    blk = nn.flash_block(s, d, 4)
    assert blk == 1024  # the block size the production path selects
    q, k, v = _rand_qkv(0, 1, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out = nn.flash_attention_tpu(q, k, v, scale, blk)
    want = _ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_flash_interpret_parity_bf16_sd_shape():
    # The production dtype on TPU: bf16 tensors, f32 softmax accumulation.
    s, d = 4096, 40
    blk = nn.flash_block(s, d, 2)
    q, k, v = _rand_qkv(1, 1, 1, s, d, jnp.bfloat16)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out = nn.flash_attention_tpu(q, k, v, scale, blk)
    want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), scale)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(want), atol=4e-2, rtol=4e-2)


def test_flash_interpret_parity_small_multiblock():
    # Fast case: S=512 with block 256 → a 2×2 block grid, several heads —
    # exercises the cross-block online-softmax reassociation cheaply.
    s, d = 512, 40
    blk = 256
    q, k, v = _rand_qkv(2, 2, 4, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out = nn.flash_attention_tpu(q, k, v, scale, blk)
    want = _ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_interpret_parity_vae_head_geometry():
    # The VAE decoder's mid-block attention runs the kernel with a single
    # 512-wide head in f32 (models/vae.py) — the widest-head site in the
    # framework. Reduced S keeps interpret mode fast; the block count (2×2)
    # still exercises the online-softmax merge at this width.
    s, d = 512, 512
    blk = 256
    q, k, v = _rand_qkv(3, 1, 1, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out = nn.flash_attention_tpu(q, k, v, scale, blk)
    want = _ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


def test_flash_interpret_grad_matches_einsum():
    """Differentiating THROUGH the flash kernel must work and match the
    materialized-attention gradient: null-text inversion backprops through
    the U-Net's S=4096 flash sites, and an under-specified BlockSizes (the
    dq backward blocks missing) raises "not all backward blocks are
    specified" at trace time — exactly how this surfaced on chip
    (2026-08-01). blk=1024 at S=1024 exercises the MIXED tiling the fix
    actually ships at the S=4096 production sites: forward blocks 1024,
    backward blocks capped at 512 — so a numeric bug specific to unequal
    forward/backward tiling (e.g. dq accumulation across the two backward
    k-blocks per forward block) dies here, not on the chip."""
    s, d = 1024, 40
    blk = 1024
    assert nn.flash_block(s, d, 4) == blk  # the production selection
    q, k, v = _rand_qkv(5, 1, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q):
        return jnp.sum(nn.flash_attention_tpu(q, k, v, scale, blk) ** 2)

    def loss_ref(q):
        return jnp.sum(_ref(q, k, v, scale) ** 2)

    with force_tpu_interpret_mode():
        g_flash = jax.grad(loss_flash)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_ref),
                               atol=1e-3, rtol=1e-3)


def test_flash_block_sizes_specify_all_backward_blocks():
    """The shared BlockSizes geometry must stay fully backward-specified —
    any future pallas field addition that reopens the trace-time error
    shows up here, not on the chip."""
    assert nn._flash_block_sizes(1024).has_backward_blocks
    assert nn._flash_block_sizes(256).has_backward_blocks


def test_flash_block_selection():
    # Tiling-only selection at the narrow SD head geometry (VMEM not binding).
    assert nn.flash_block(4096, 40, 2) == 1024
    assert nn.flash_block(2048, 40, 2) == 1024
    assert nn.flash_block(1024, 40, 2) == 1024
    assert nn.flash_block(768, 40, 2) == 256
    assert nn.flash_block(1000, 40, 2) == 0  # not tileable → einsum path
    # Scoped-VMEM-aware selection: the SD U-Net 64² site (bf16, D=40) keeps
    # the largest block; the VAE mid-attention shape (f32, D=512) must step
    # down — block 1024 there is the 19 MiB > 16 MiB compile-time OOM that
    # killed the g≥4 sweep legs on the chip.
    assert nn.flash_block(4096, 40, 2) == 1024
    assert nn.flash_block(4096, 512, 4) == 512
    assert nn.flash_block(4096, 512, 2) == 1024  # bf16 halves the footprint
    # Absurdly wide heads: no viable block → 0 → einsum/XLA path.
    assert nn.flash_block(4096, 4096, 4) == 0


def test_flash_residuals_semantics():
    # (out, l, m) from the residuals variant: out normalized, l = row sum of
    # exp(s - m), m = row max — the invariants ring attention's merge relies
    # on (parallel/ring.py _block_attend use_flash path).
    s, d = 512, 40
    blk = 256
    q, k, v = _rand_qkv(4, 1, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out, l, m = nn.flash_attention_residuals(q, k, v, scale, blk)
    sim = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) * scale
    m_ref = sim.max(-1)
    p = np.exp(sim - m_ref[..., None])
    l_ref = p.sum(-1)
    out_ref = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v)) / l_ref[..., None]
    np.testing.assert_allclose(np.asarray(m), m_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l), l_ref, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), out_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_ring_attention_flash_chunks_parity():
    # Flash-chunked ring vs einsum-chunked ring vs single-device reference,
    # on a 4-device CPU mesh with 1024-pixel local chunks (the production
    # long-context configuration, interpret mode standing in for TPU).
    from jax.sharding import Mesh
    from p2p_tpu.parallel.ring import ring_self_attention

    devs = jax.devices("cpu")[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("sp",))
    s, d = 4096, 40
    q, k, v = _rand_qkv(5, 1, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    want = _ref(q, k, v, scale)
    ring_einsum = ring_self_attention(q, k, v, scale, mesh, "sp",
                                      use_flash=False)
    np.testing.assert_allclose(np.asarray(ring_einsum), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    with force_tpu_interpret_mode():
        ring_flash = ring_self_attention(q, k, v, scale, mesh, "sp",
                                         use_flash=True)
    np.testing.assert_allclose(np.asarray(ring_flash), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_ring_attention_flash_grad_falls_back_to_einsum():
    # The flash chunk's custom VJP recomputes through the einsum block, so a
    # differentiated sequence-parallel site (e.g. inversion under SpConfig)
    # keeps working when use_flash=True.
    from jax.sharding import Mesh
    from p2p_tpu.parallel.ring import ring_self_attention

    devs = jax.devices("cpu")[:2]
    mesh = Mesh(np.asarray(devs).reshape(2), ("sp",))
    s, d = 2048, 8  # local chunks of 1024 → flash-tileable
    q, k, v = _rand_qkv(6, 1, 1, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def loss(fn_flash):
        def f(q):
            out = ring_self_attention(q, k, v, scale, mesh, "sp",
                                      use_flash=fn_flash)
            return jnp.sum(out * out)
        return f

    g_einsum = jax.grad(loss(False))(q)
    with force_tpu_interpret_mode():
        g_flash = jax.grad(loss(True))(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_einsum),
                               atol=1e-4, rtol=1e-4)


def test_ring_attention_flash_nontileable_falls_back():
    # use_flash=True with a non-tileable local chunk (250 pixels) must take
    # the einsum path instead of building a zero-size Pallas grid.
    from jax.sharding import Mesh
    from p2p_tpu.parallel.ring import ring_self_attention

    devs = jax.devices("cpu")[:2]
    mesh = Mesh(np.asarray(devs).reshape(2), ("sp",))
    s, d = 500, 8
    q, k, v = _rand_qkv(7, 1, 1, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    out = ring_self_attention(q, k, v, scale, mesh, "sp", use_flash=True)
    want = _ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_ring_merge_matches_full_softmax_any_block_count():
    # The (acc, m, l) log-sum-exp merge must reproduce the full softmax over
    # concatenated k/v for any split — the invariant the ppermute ring rests
    # on (parallel/ring.py _merge).
    from p2p_tpu.parallel.ring import _block_attend, _merge

    rng = np.random.RandomState(8)
    b, h, sq, d = 1, 2, 64, 8
    q = jnp.asarray(rng.randn(b, h, sq, d).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    for n_blocks in (2, 3, 5):
        ks = [jnp.asarray(rng.randn(b, h, 32, d).astype(np.float32))
              for _ in range(n_blocks)]
        vs = [jnp.asarray(rng.randn(b, h, 32, d).astype(np.float32))
              for _ in range(n_blocks)]
        acc, m, l = _block_attend(q, ks[0], vs[0], scale)
        for k, v in zip(ks[1:], vs[1:]):
            acc, m, l = _merge(acc, m, l, *_block_attend(q, k, v, scale))
        got = np.asarray(acc / l[..., None])
        want = np.asarray(_ref(q, jnp.concatenate(ks, axis=2),
                               jnp.concatenate(vs, axis=2), scale))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
