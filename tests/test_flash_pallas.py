"""Pallas flash-attention kernel parity, interpret mode (CPU).

The untouched self-attention sites with 1024 keys or more (64² and 32²
pixels of SD-1.4, the VAE decoder's mid block) run the library's Pallas TPU
flash kernel via `nn.flash_attention_tpu` (`p2p_tpu/models/nn.py`) — a path
the CPU test suite otherwise never executes. `force_tpu_interpret_mode()`
executes the *identical* kernel — same BlockSizes, same grid, the softmax
scale folded into `q` — in the Pallas interpreter on CPU, so parity against
the materialized `attention_probs` + einsum reference is checked in CI at
every row of the geometry table `nn.flash_block` answers from. Batch and
heads are reduced (the kernel grid iterates them independently; geometry per
batch·head is what the blocks tile).

Tolerance: the kernel accumulates softmax/matmul in f32 like the reference
path, but blockwise online-softmax reassociates the sums — f32 operands agree
to ~1e-5; bf16 operands to a few 1e-2 in absolute terms on O(1)-scale outputs.

Operand width (PR 35): at the process's default matmul precision
`flash_attention_tpu` hands the kernel float32 arrays as bfloat16 (what the
MXU multiplied them as anyway) and returns float32. So the parity tests run
in both forms (`form`): ``narrowed`` is the production call and agrees to
bf16 tolerance; ``highest`` (a process that set
``jax_default_matmul_precision``) is the parent's call on f32 operands and
keeps the 1e-5 that pins the tiling arithmetic. The interpreter multiplies
f32 exactly, so that the chip's one-pass products lose nothing by the
narrowing is the chip's check (PERF.md §6, PR 35), not the CPU's.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental.pallas.tpu import force_tpu_interpret_mode
from p2p_tpu.models import LDM256, SD14, SD21, nn
from p2p_tpu.models.config import unet_layout


def _ref(q, k, v, scale):
    probs = nn.attention_probs(q, k, scale).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _rand_qkv(seed, b, h, s, d, dtype):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d), dtype=dtype)
    return mk(), mk(), mk()


FORMS = ("narrowed", "highest")


def _precision(name):
    """A process that set ``jax_default_matmul_precision`` to ``name``; its
    own default for None."""
    return (jax.default_matmul_precision(name) if name
            else contextlib.nullcontext())


def _form(form):
    """The process a call is traced in: its own default, or one that asked
    for ``highest`` products (f32 operands then reach the kernel)."""
    return _precision("highest" if form == "highest" else None)


def _tol(form, dtype, f32_tol=1e-5):
    narrow = dtype == jnp.bfloat16 or form == "narrowed"
    return 4e-2 if narrow else f32_tol


#: (keys, head size, dtype): the shape classes of the geometry table — the
#: 64² and 32² self sites of SD-1.4, the 32² site of LDM-256, the VAE
#: decoder's mid attention (one 512-wide head, f32), the bf16 sweep's 64² site.
TABLE_ROWS = [(4096, 40, jnp.float32), (1024, 80, jnp.float32),
              (1024, 64, jnp.float32), (4096, 512, jnp.float32),
              (4096, 40, jnp.bfloat16)]


def _row_id(row):
    return f"S{row[0]}-d{row[1]}-{jnp.dtype(row[2]).name}"


def _geometry(s, d, dtype):
    """The table's answer for arrays of ``dtype`` in the process as it is:
    asked at the width the kernel is handed them in, as the sites ask."""
    return nn.flash_block(s, d, nn.flash_operand_dtype(dtype).itemsize)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("row", TABLE_ROWS, ids=_row_id)
def test_flash_interpret_parity_at_table_row(row, form):
    s, d, dtype = row
    q, k, v = _rand_qkv(0, 1, 2 if d < 512 else 1, s, d, dtype)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode(), _form(form):
        geometry = _geometry(s, d, dtype)
        assert geometry is not None     # the production path takes the kernel
        out = nn.flash_attention_tpu(q, k, v, scale, geometry)
    assert out.dtype == dtype
    want = _ref(*(t.astype(jnp.float32) for t in (q, k, v)), scale)
    tol = _tol(form, dtype)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("form", FORMS)
def test_flash_interpret_parity_small_multiblock(form):
    # Fast case: S=512 with block 256 → a 2×2 block grid, several heads —
    # exercises the cross-block online-softmax reassociation cheaply.
    s, d = 512, 40
    q, k, v = _rand_qkv(2, 2, 4, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode(), _form(form):
        out = nn.flash_attention_tpu(q, k, v, scale, (256, 256, 256))
    want = _ref(q, k, v, scale)
    tol = _tol(form, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("form", FORMS)
def test_flash_interpret_parity_vae_head_geometry(form):
    # The VAE decoder's mid-block attention runs the kernel with a single
    # 512-wide head in f32 (models/vae.py) — the widest-head site in the
    # framework. Reduced S keeps interpret mode fast; the block count (2×2)
    # still exercises the online-softmax merge at this width.
    s, d = 512, 512
    q, k, v = _rand_qkv(3, 1, 1, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode(), _form(form):
        out = nn.flash_attention_tpu(q, k, v, scale, (256, 256, 256))
    want = _ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=_tol(form, jnp.float32, 1e-4), rtol=1e-5)


#: (keys, head size, tile): the four kernel sites of the two cells at toy
#: lengths, heads of 40 and 80 (`sd14`), 64 and 64 (`sd21`, whose lengths
#: are 9 x 2^n), each a grid of several q and k blocks.
NARROWED_SITES = [(512, 40, (256, 512, 256)), (512, 80, (256, 256, 256)),
                  (1152, 64, (384, 1152, 384)), (768, 64, (384, 768, 768))]


def _hand_rounded(q, k, v, scale, geometry):
    """What ``flash_attention_tpu`` says it does to f32 arrays, spelled out:
    the library kernel on ``q * scale``, ``k``, ``v`` rounded to bfloat16,
    its output cast back."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    bf = jnp.bfloat16
    out = fa.flash_attention((q * scale).astype(bf), k.astype(bf), v.astype(bf),
                             causal=False, sm_scale=1.0,
                             block_sizes=nn._flash_block_sizes(geometry))
    return out.astype(q.dtype)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("site", NARROWED_SITES,
                         ids=lambda t: f"S{t[0]}-d{t[1]}")
def test_flash_f32_arrays_reach_the_kernel_as_bfloat16(site):
    """f32 in, f32 out; bit for bit the kernel on operands rounded by hand
    and cast back; and within one bf16 rounding of the operands of a
    ``Precision.HIGHEST`` einsum reference (2^-9 a rounding, three operands
    and the output: a relative error of a few 1e-3 of the output's norm, and
    not the 1e-6 of exact operands either, so the rounding is seen)."""
    s, d, geometry = site
    q, k, v = _rand_qkv(11, 2, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out = nn.flash_attention_tpu(q, k, v, scale, geometry)
        by_hand = _hand_rounded(q, k, v, scale, geometry)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(by_hand))
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(
        jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) * scale, axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=hi)
    assert 1e-4 < _rel(out, want) < 2 ** -7


@pytest.mark.parametrize("site", NARROWED_SITES[::2],
                         ids=lambda t: f"S{t[0]}-d{t[1]}")
def test_flash_grad_through_the_narrowed_call(site):
    """``jax.grad`` through the new form is the gradient of the hand-rounded
    chain bit for bit (the casts' transposes round the cotangent going in and
    widen dq, dk, dv coming out), f32 like its arguments."""
    s, d, geometry = site
    q, k, v = _rand_qkv(12, 1, 2, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def grads(call):
        return jax.grad(lambda q, k, v: jnp.sum(call(q, k, v, scale, geometry) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    with force_tpu_interpret_mode():
        got, by_hand = grads(nn.flash_attention_tpu), grads(_hand_rounded)
    for g, h, arg in zip(got, by_hand, (q, k, v)):
        assert g.dtype == arg.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(h))


#: (arrays' dtype, jax_default_matmul_precision, dtype the kernel is handed)
OPERAND_CASES = [
    (jnp.float32, None, jnp.bfloat16), (jnp.float32, "default", jnp.bfloat16),
    (jnp.float32, "bfloat16", jnp.bfloat16), (jnp.float32, "tensorfloat32", jnp.float32),
    (jnp.float32, "highest", jnp.float32), (jnp.float32, "float32", jnp.float32),
    (jnp.float32, "high", jnp.float32), (jnp.float32, "BF16_BF16_F32", jnp.float32),
    (jnp.bfloat16, None, jnp.bfloat16), (jnp.bfloat16, "highest", jnp.bfloat16),
    (jnp.float16, None, jnp.float16),
]


@pytest.mark.parametrize(
    "case", OPERAND_CASES,
    ids=lambda c: f"{jnp.dtype(c[0]).name}-{c[1]}")
def test_flash_operand_width_follows_dtype_and_precision(case, monkeypatch):
    """Only f32 arrays in a process at the default matmul precision are
    narrowed. A process that asked for more (its setting reaches the kernel's
    own products) and arrays that are bfloat16 already take the parent's
    call: operands and output in the arrays' dtype, ``q`` scaled, the same
    BlockSizes. The output is the arrays' dtype in every case."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    dtype, precision, operand = case
    handed = []

    def kernel(q, k, v, **kw):
        handed.append(((q.dtype, k.dtype, v.dtype), kw))
        return q

    monkeypatch.setattr(fa, "flash_attention", kernel)
    q = jax.ShapeDtypeStruct((2, 2, 1024, 40), dtype)
    with _precision(precision):
        assert nn.flash_operand_dtype(dtype) == operand
        out = jax.eval_shape(
            lambda q, k, v: nn.flash_attention_tpu(q, k, v, 0.25, (512, 512, 512)),
            q, q, q)
    assert out.dtype == dtype and out.shape == q.shape
    (dtypes, kw), = handed
    assert dtypes == (operand,) * 3
    assert kw == dict(causal=False, sm_scale=1.0,
                      block_sizes=nn._flash_block_sizes((512, 512, 512)))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("row", TABLE_ROWS[:4], ids=_row_id)
def test_flash_interpret_grad_matches_einsum(row, form):
    """Differentiating THROUGH the flash kernel must work and match the
    materialized-attention gradient: null-text inversion backprops through
    the U-Net's flash sites, and an under-specified BlockSizes (the
    dq backward blocks missing) raises "not all backward blocks are
    specified" at trace time — exactly how this surfaced on chip
    (2026-08-01). Every row runs the MIXED tiling the production sites
    ship: the table's forward geometry, backward blocks capped at 512 — so
    a numeric bug specific to unequal forward/backward tiling (e.g. dq
    accumulation across the backward k-blocks per forward block), or in the
    scale folded into q ahead of the custom VJP, dies here, not on the
    chip."""
    s, d, dtype = row
    q, k, v = _rand_qkv(5, 1, 1, s, d, dtype)
    scale = 1.0 / np.sqrt(d)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, scale) ** 2)

    with force_tpu_interpret_mode(), _form(form):
        geometry = _geometry(s, d, dtype)

        def loss_flash(q, k, v):
            return jnp.sum(nn.flash_attention_tpu(q, k, v, scale, geometry) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(g_flash, g_ref, form)


def _assert_grads_close(g_flash, g_ref, form):
    """f32 operands: element by element, as ever. Narrowed operands: the
    gradients carry the operands' and the cotangent's bf16 roundings, so each
    is held to a hundredth of the reference's norm (a missing scale or a
    wrong backward block is off by its whole size)."""
    for got, want in zip(g_flash, g_ref):
        assert got.dtype == want.dtype
        if form == "highest":
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-3, rtol=1e-3)
        else:
            assert _rel(got, want) < 1e-2


def test_flash_block_sizes_specify_all_backward_blocks():
    """The shared BlockSizes geometry must stay fully backward-specified —
    any future pallas field addition that reopens the trace-time error
    shows up here, not on the chip."""
    for s, d, dtype in TABLE_ROWS:
        geometry = nn.flash_block(s, d, jnp.dtype(dtype).itemsize)
        sizes = nn._flash_block_sizes(geometry)
        assert sizes.has_backward_blocks
        assert (sizes.block_q, sizes.block_k_major, sizes.block_k) == geometry
        assert sizes.block_q_dq == min(geometry[1], 512)     # capped as ever
    assert nn._flash_block_sizes((256, 256, 256)).has_backward_blocks


def test_flash_block_selection():
    # Below 1024 keys there is no geometry (the einsum chain is faster there);
    # from 1024 up the sweep's geometry for the narrow SD head.
    assert nn.flash_block(4096, 40, 2) == (256, 4096, 2048)  # K, V resident
    assert nn.flash_block(2048, 40, 2) == (512, 2048, 1024)
    assert nn.flash_block(1024, 40, 2) == (1024, 1024, 1024)
    assert nn.flash_block(768, 40, 2) is None
    assert nn.flash_block(1000, 40, 2) is None  # not tileable → einsum path
    # Scoped-VMEM-aware selection: the SD U-Net 64² site (bf16, D=40) keeps
    # the sweep's first choice; the VAE mid-attention shape (f32, D=512) must
    # step down — block 1024 there is the 19 MiB > 16 MiB compile-time OOM
    # that killed the g≥4 sweep legs on the chip.
    assert nn.flash_block(4096, 40, 4) == (256, 4096, 2048)
    assert nn.flash_block(16384, 40, 4) == (512, 2048, 1024)
    assert nn.flash_block(4096, 512, 4) == (512, 512, 512)
    assert nn.flash_block(4096, 512, 2) == (512, 512, 512)
    # Absurdly wide heads: no viable block → None → einsum path; so is a
    # head the library's online body refuses (over 128 and no multiple).
    assert nn.flash_block(4096, 4096, 4) is None
    assert nn.flash_block(4096, 160, 4) is None


#: SD-2.1 at 768²: the 48² and 96² self sites, 9 x 2^n keys (PR 29).
SD21_ROWS = [(2304, 64, jnp.float32), (9216, 64, jnp.float32)]


def test_flash_block_selection_at_sd21_lengths():
    # The measured geometries of PERF.md §6, PR 29: K and V resident at 2304
    # keys; at 9216 they do not fit, and a wide q block streams them least.
    # At 2 B (PR 35's sweep) a 4608-key score tile fits beside them, at 4 B
    # it does not and the row's second entry answers.
    assert nn.flash_block(2304, 64, 4) == (768, 2304, 1152)
    assert nn.flash_block(2304, 64, 2) == (768, 2304, 1152)
    assert nn.flash_block(9216, 64, 4) == (512, 3072, 1536)
    assert nn.flash_block(9216, 64, 2) == (512, 4608, 1536)
    assert nn.flash_block(9216, 512, 4) == (512, 512, 512)     # the VAE at 96²
    assert nn.flash_block(576, 64, 4) is None                  # einsum chain
    # A length's own geometry still passes the guard: a head of 256 at 2304
    # keys does not fit K and V resident and steps down the general list.
    assert nn.flash_block(2304, 256, 4) == (256, 256, 256)
    # Backward blocks tile the length: 512 divides no multiple of 2304.
    assert nn._flash_block_sizes((768, 2304, 1152)).block_q_dq == 384
    assert nn._flash_block_sizes((512, 3072, 1536)).block_q_dq == 512
    for geometry in ((768, 2304, 1152), (512, 3072, 1536), (512, 4608, 1536)):
        sizes = nn._flash_block_sizes(geometry)
        assert sizes.has_backward_blocks
        assert geometry[1] % sizes.block_k_major_dkv == 0


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("row", SD21_ROWS, ids=_row_id)
def test_flash_interpret_parity_at_sd21_row(row, form):
    s, d, dtype = row
    q, k, v = _rand_qkv(3, 1, 1, s, d, dtype)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode(), _form(form):
        out = nn.flash_attention_tpu(q, k, v, scale, _geometry(s, d, dtype))
    tol = _tol(form, dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, scale)),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("form", FORMS)
def test_flash_interpret_grad_at_2304_keys(form):
    """The backward blocks of 384 (no 512 tiles 2304): gradients through the
    kernel at SD-2.1's 48² site match the materialized attention's."""
    s, d = 2304, 64
    q, k, v = _rand_qkv(7, 1, 1, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode(), _form(form):
        geometry = _geometry(s, d, jnp.float32)
        g_flash = jax.grad(lambda q, k, v: jnp.sum(
            nn.flash_attention_tpu(q, k, v, scale, geometry) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(_ref(q, k, v, scale) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(g_flash, g_ref, form)


def _site_shapes(config):
    """``{(pixels, head size)}`` of a preset's self-attention sites, plus its
    VAE decoder's mid attention (one head as wide as the last level)."""
    shapes = {(m.pixels, m.channels // m.heads)
              for m in unet_layout(config.unet).metas if not m.is_cross}
    vae = config.vae
    return shapes | {(config.latent_size ** 2,
                      vae.base_channels * vae.channel_mults[-1])}


def test_flash_geometries_stay_inside_the_vmem_budget():
    # Whatever the table answers tiles the sequence and passes the guard,
    # over every shape a preset can ask about and both carrier dtypes.
    lengths = (256, 768, 1000, 1024, 2048, 4096, 9216, 16384)
    for s in lengths:
        for d in (8, 40, 64, 80, 160, 512, 4096):
            for itemsize in (2, 4):
                geometry = nn.flash_block(s, d, itemsize)
                if geometry is None:
                    continue
                block_q, block_k_major, block_k = geometry
                assert s % block_q == 0 and s % block_k_major == 0
                assert block_k_major % block_k == 0
                assert nn._flash_vmem_bytes(geometry, d, itemsize) \
                    <= nn._FLASH_VMEM_BUDGET


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("config", [SD14, LDM256, SD21], ids=lambda c: c.name)
def test_fused_attention_follows_the_table(config, form, monkeypatch):
    """``fused_attention`` takes the kernel exactly where the table has a
    geometry for the site's shape, and the einsum chain elsewhere — from the
    shapes alone, for every site shape of SD-1.4, LDM-256 and SD-2.1 at 768²
    (9216 and 2304 keys on the kernel, 576 and 144 on the chain). The table
    is asked at the width the kernel is handed the f32 arrays in: 2 B, or 4
    in a process that set ``highest``."""
    with _form(form):
        _follows_the_table(config, 2 if form == "narrowed" else 4, monkeypatch)


def _follows_the_table(config, itemsize, monkeypatch):
    taken = []
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        nn, "flash_attention_tpu",
        lambda q, k, v, scale, geometry: taken.append(geometry) or _ref(q, k, v, scale))
    for pixels, d_head in sorted(_site_shapes(config)):
        q = jax.ShapeDtypeStruct((2, 1, pixels, d_head), jnp.float32)
        want = nn.flash_block(pixels, d_head, itemsize)
        assert (want is not None) == (pixels >= 1024), (pixels, d_head)
        del taken[:]
        jax.eval_shape(lambda q, k, v: nn.fused_attention(q, k, v, 0.1), q, q, q)
        assert taken == ([want] if want else []), (pixels, d_head)
        assert nn.takes_flash_kernel(pixels, d_head, itemsize) == bool(want)
        # a mask, or keys of another length (cross-attention), never do
        del taken[:]
        ctx = jax.ShapeDtypeStruct((2, 1, 77, d_head), jnp.float32)
        mask = jax.ShapeDtypeStruct((1, 1, pixels, pixels), jnp.float32)
        jax.eval_shape(lambda q, c: nn.fused_attention(q, c, c, 0.1), q, ctx)
        jax.eval_shape(lambda q, m: nn.fused_attention(q, q, q, 0.1, m), q, mask)
        assert taken == []
    # off the TPU nothing takes the kernel
    monkeypatch.setattr(nn, "_on_tpu", lambda: False)
    jax.eval_shape(lambda q: nn.fused_attention(q, q, q, 0.1),
                   jax.ShapeDtypeStruct((2, 1, 4096, 40), jnp.float32))
    assert taken == [] and not nn.takes_flash_kernel(4096, 40, 4)


@pytest.mark.parametrize("row", TABLE_ROWS[:4] + [(512, 40, jnp.float32)],
                         ids=_row_id)
def test_flash_residuals_semantics(row):
    # (out, l, m) from the residuals variant: out normalized, l = row sum of
    # exp(s - m), m = row max of the SCALED logits — the invariants ring
    # attention's merge relies on (parallel/ring.py _block_attend use_flash
    # path), at every table row and at a small 2×2 block grid.
    s, d, dtype = row
    geometry = (nn.flash_block(s, d, jnp.dtype(dtype).itemsize)
                or (256, 256, 256))
    q, k, v = _rand_qkv(4, 1, 1, s, d, dtype)
    scale = 1.0 / np.sqrt(d)
    with force_tpu_interpret_mode():
        out, l, m = nn.flash_attention_residuals(q, k, v, scale, geometry)
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
