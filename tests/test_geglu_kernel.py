"""The GEGLU feed-forward kernel (``kernels/geglu.py``), interpret mode (CPU).

The kernel computes ``x + ff_out(val · gelu(gate))`` from the layer-normed
rows in one call, the normed rows and the weights handed over in bfloat16
(what the chip's MXU multiplies f32 operands in at the default precision).
The interpreter multiplies exactly, so:

- against a reference whose operands are rounded to bfloat16 by hand the
  kernel agrees to f32 reassociation (the order of ``ff_out``'s sums and the
  rational erf), which pins its tiling arithmetic;
- against XLA's formula on f32 operands it agrees to the bf16 operand
  rounding, which is the chip's formula.

Shapes are small, at the cells' ratios: inner = 4 · channels, channels in
1 : 2 : 4 as 320 / 640 / 1280, kernels stored in f32 (`sd14`, `sd21`) and
bfloat16 (`sdxl`). The gradient is the formula's (``custom_vjp``); under
``vmap`` the call batches over a grid axis; ``nn.ff_block`` never answers a
tile over its scoped-VMEM budget; ``geglu.plan`` decides by shape, platform
and mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.kernels import geglu
from p2p_tpu.models import nn
from p2p_tpu.obs import launches

#: (rows, channels, tile): channels 32 / 64 / 128 stand for 320 / 640 / 1280.
SHAPES = [(256, 32, (128, 128)), (128, 64, (64, 128)), (64, 128, (32, 256))]
KERNEL_DTYPES = (jnp.float32, jnp.bfloat16)


def _block(rows, channels, kernel_dtype, seed=0, groups=None):
    inner = 4 * channels
    lead = (rows // 4, 4) if groups is None else (groups, rows // 4, 4)
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], lead + (channels,))
    normed = jax.random.normal(k[1], lead + (channels,))
    p_in = nn.linear_init(k[2], channels, 2 * inner, kernel_dtype=kernel_dtype)
    p_in["bias"] = 0.1 * jax.random.normal(k[3], (2 * inner,))
    p_out = nn.linear_init(k[4], inner, channels, kernel_dtype=kernel_dtype)
    p_out["bias"] = 0.1 * jax.random.normal(k[5], (channels,))
    return x, normed, p_in, p_out


def _rounded(x, normed, p_in, p_out):
    """The formula with every MXU operand rounded to bfloat16 by hand, the
    products exact: what the chip computes."""
    def bf(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = bf(normed) @ bf(p_in["kernel"]) + p_in["bias"]
        val, gate = jnp.split(h, 2, axis=-1)
        return x + (bf(val * nn.gelu(gate)) @ bf(p_out["kernel"])
                    + p_out["bias"])


def _rel(a, b, x):
    """Relative error of the feed-forward's part (the residual taken off)."""
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b - x))


def _kernel(tile):
    return jax.jit(lambda x, n, a, b: geglu.geglu_feed_forward(
        x, n, a, b, tile, True))


@pytest.mark.parametrize("kernel_dtype", KERNEL_DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("rows,channels,tile", SHAPES)
def test_forward_matches_the_formula(rows, channels, tile, kernel_dtype):
    args = _block(rows, channels, kernel_dtype)
    out = _kernel(tile)(*args)
    assert out.dtype == jnp.float32 and out.shape == args[0].shape
    x = args[0]
    # the kernel's own arithmetic: f32 reassociation
    assert _rel(out, _rounded(*args), x) < 1e-4
    # against f32 products: the bfloat16 rounding of the operands, no more
    assert _rel(out, geglu.feed_forward_formula(*args), x) < 2.0 ** -7


def test_gelu_is_the_exact_one():
    z = jnp.linspace(-8.0, 8.0, 4001)
    assert float(jnp.max(jnp.abs(geglu._gelu(z) - nn.gelu(z)))) < 2e-6


@pytest.mark.parametrize("kernel_dtype", KERNEL_DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_gradient_is_the_formulas(kernel_dtype):
    rows, channels, tile = SHAPES[0]
    args = _block(rows, channels, kernel_dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn):
        # linear in the output: the cotangent is the same on both sides
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=(0, 1, 2, 3)))

    got = loss(lambda *a: geglu.geglu_feed_forward(*a, tile, True))(*args)
    want = loss(geglu.feed_forward_formula)(*args)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), rtol=1e-6,
                                   atol=1e-6)


def test_vmap_over_groups():
    rows, channels, tile = SHAPES[1]
    x, normed, p_in, p_out = _block(rows, channels, jnp.bfloat16, groups=3)
    batched = jax.jit(jax.vmap(
        lambda x, n: geglu.geglu_feed_forward(x, n, p_in, p_out, tile, True)))
    out = batched(x, normed)
    one = _kernel(tile)
    for g in range(3):
        np.testing.assert_allclose(
            np.asarray(out[g]), np.asarray(one(x[g], normed[g], p_in, p_out)),
            rtol=1e-6, atol=1e-6)
    assert _rel(out, _rounded(x, normed, p_in, p_out), x) < 1e-4


def test_vmap_of_the_gradient():
    """``sweep``'s groups differentiated through (null-text under a group
    axis): vmap over the custom_vjp's backward."""
    rows, channels, tile = SHAPES[1]
    x, normed, p_in, p_out = _block(rows, channels, jnp.float32, groups=2)

    def grad_of(fn):
        return jax.jit(jax.vmap(jax.grad(
            lambda x, n: jnp.sum(fn(x, n, p_in, p_out)), argnums=1)))

    got = grad_of(lambda *a: geglu.geglu_feed_forward(*a, tile, True))(x, normed)
    want = grad_of(geglu.feed_forward_formula)(x, normed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


#: Every (pixels, channels, inner) of one image's block the three cells run,
#: and a grid around them.
CELL_SHAPES = [(4096, 320, 1280), (1024, 640, 2560), (256, 1280, 5120),
               (64, 1280, 5120), (9216, 320, 1280), (2304, 640, 2560),
               (576, 1280, 5120), (144, 1280, 5120), (4096, 640, 2560),
               (1024, 1280, 5120)]
GRID = [(pixels, c, m * c) for pixels in (16, 64, 144, 256, 576, 1024, 2304,
                                          4096, 9216, 16384)
        for c in (32, 320, 640, 1280, 2560) for m in (2, 4, 8)]


@pytest.mark.parametrize("itemsize", (2, 4))
def test_ff_block_stays_inside_its_budget(itemsize):
    answered = 0
    for pixels, channels, inner in set(CELL_SHAPES + GRID):
        tile = nn.ff_block(pixels, channels, inner, itemsize)
        if tile is None:
            continue
        answered += 1
        block_rows, chunk = tile
        assert pixels % block_rows == 0 and inner % chunk == 0
        assert nn._ff_vmem_bytes(tile, channels, itemsize) <= nn._FF_VMEM_BUDGET
    # the table's rows and nothing else; at the kernel's own width all of them
    assert 0 < answered <= len(nn._FF_BY_SHAPE)
    assert itemsize != 2 or answered == len(nn._FF_BY_SHAPE)
    assert nn._FF_VMEM_BUDGET < nn._FF_VMEM_LIMIT


def test_ff_block_answers_only_the_table(monkeypatch):
    assert nn.ff_block(1024, 1280, 5120, 2) == nn._FF_BY_SHAPE[(1024, 1280, 5120)]
    assert nn.ff_block(4096, 320, 1280, 2) is None       # `sd14`'s 64² block
    # a row over the budget, or a chunk that does not divide the inner width
    monkeypatch.setattr(nn, "_FF_BY_SHAPE", {(64, 2560, 10240): (8192, 2560),
                                             (64, 32, 96): (64, 64)})
    assert nn.ff_block(64, 2560, 10240, 2) is None
    assert nn.ff_block(64, 32, 96, 2) is None


def test_ff_vmem_count_grows_with_every_part():
    base = nn._ff_vmem_bytes((256, 256), 640, 2)
    assert nn._ff_vmem_bytes((512, 256), 640, 2) > base
    assert nn._ff_vmem_bytes((256, 512), 640, 2) > base
    assert nn._ff_vmem_bytes((256, 256), 1280, 2) > base
    # channels are lane-padded: 320 counts as 384
    assert nn._ff_vmem_bytes((256, 256), 320, 2) == nn._ff_vmem_bytes(
        (256, 256), 384, 2)


def _plan_args(rows=4096, channels=1280, dtype=jnp.float32):
    inner = 4 * channels
    x = jnp.zeros((4, rows // 4, channels), dtype)
    p_in = {"kernel": jnp.zeros((channels, 2 * inner), jnp.bfloat16),
            "bias": jnp.zeros((2 * inner,))}
    p_out = {"kernel": jnp.zeros((inner, channels), jnp.bfloat16),
             "bias": jnp.zeros((channels,))}
    return x, p_in, p_out


def test_plan_off_tpu_is_the_formula():
    assert geglu.plan(*_plan_args()) == ("formula", None)


def test_plan_on_tpu_by_shape_and_dtype(monkeypatch):
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    x, p_in, p_out = _plan_args()
    assert geglu.plan(x, p_in, p_out) == (
        "kernel", nn.ff_block(1024, 1280, 5120, 2))
    # bf16 arrays: the formula's products are bf16 ones, its GELU too
    assert geglu.plan(x.astype(jnp.bfloat16), p_in, p_out) == ("formula", None)
    # a process that asked for f32 products
    with jax.default_matmul_precision("highest"):
        assert geglu.plan(x, p_in, p_out) == ("formula", None)
    # a shape not in the table
    assert geglu.plan(*_plan_args(rows=4 * 7, channels=24)) == ("formula", None)
    # a row tile that does not divide the rows
    monkeypatch.setattr(nn, "_FF_BY_SHAPE", {(48, 24, 96): (128, 96)})
    x, p_in, p_out = _plan_args(rows=8 * 48, channels=24)
    x = x.reshape(8, 48, 24)
    assert geglu.plan(x[:4], p_in, p_out) == ("formula", None)
    assert geglu.plan(x, p_in, p_out) == ("kernel", (128, 96))


#: Each cell's blocks, one image's (pixels, channels): the table's decision
#: holds at any batch (prompts × CFG, a serve pool's slots).
CELL_BLOCKS = {"sd14": ((4096, 320), (1024, 640), (256, 1280), (64, 1280)),
               "sd21": ((9216, 320), (2304, 640), (576, 1280), (144, 1280)),
               "sdxl": ((4096, 640), (1024, 1280))}


@pytest.mark.parametrize("batch", (1, 2, 6))
@pytest.mark.parametrize("name", sorted(CELL_BLOCKS))
def test_plan_decides_per_image(name, batch, monkeypatch):
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    for pixels, channels in CELL_BLOCKS[name]:
        x, p_in, p_out = _plan_args(batch * pixels, channels)
        x = x.reshape(batch, pixels, channels)
        how, tile = geglu.plan(x, p_in, p_out)
        if name == "sdxl":
            assert (how, tile) == ("kernel", nn._FF_BY_SHAPE[
                (pixels, channels, 4 * channels)])
        else:
            assert (how, tile) == ("formula", None)


def test_plan_keeps_the_formula_on_a_tp_mesh(monkeypatch):
    from p2p_tpu.parallel import make_mesh, shard_params

    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    x, p_in, p_out = _plan_args()
    seen = []

    def trace(mesh):
        params = shard_params({"ff_in": p_in, "ff_out": p_out}, mesh)
        jax.jit(lambda x, p: seen.append(geglu.plan(x, p["ff_in"], p["ff_out"]))
                or x).lower(x, params)
        return seen.pop()

    assert trace(make_mesh(8, tp=2)) == ("sharded", None)
    # a dp mesh partitions nothing of the block's weights, but the kernel runs
    # per device only where nn.kernel_mesh wraps it
    dp = make_mesh(4, tp=1)
    assert trace(dp) == ("sharded", None)
    with nn.kernel_mesh(dp):
        assert trace(dp)[0] == "kernel"


def test_launch_line_counts_the_blocks():
    launches.built()
    launches.note_ff_site(2, "kernel", 4096, 1280, 5120, (512, 512))
    launches.note_ff_site(4, "kernel", 4096, 1280, 5120, (512, 512))
    launches.note_ff_site(4, "kernel", 4096, 1280, 5120, (512, 512))  # traced twice
    launches.note_ff_site(6, "formula", 16384, 640, 2560)
    launch = launches.Launch("jit_f", None, (), {},
                             ff_sites=dict(launches._traced_ff))
    assert launch.describe_ff() == (
        "ff {'kernel': 2, 'formula': 1}; 2 of 4096x1280x5120 kernel 512x512; "
        "1 of 16384x640x2560 formula")
    launches.built()
    assert launches._traced_ff == {}


#: How each cell's sampling program runs its blocks' feed-forwards on the
#: chip, as ``nn.ff_block``'s table decides: ``{(rows, channels, inner,
#: how): blocks}``.
CELL_FF = {
    "sd14": {(16384, 320, 1280, "formula"): 5, (4096, 640, 2560, "formula"): 5,
             (1024, 1280, 5120, "formula"): 5, (256, 1280, 5120, "formula"): 1},
    "sd21": {(36864, 320, 1280, "formula"): 5, (9216, 640, 2560, "formula"): 5,
             (2304, 1280, 5120, "formula"): 5, (576, 1280, 5120, "formula"): 1},
    "sdxl": {(4096, 1280, 5120, "kernel"): 60, (16384, 640, 2560, "kernel"): 10},
}


@pytest.mark.parametrize("name", sorted(CELL_FF))
def test_cell_programs_take_the_table(name, monkeypatch):
    """The cells' sampling programs traced (not compiled) at their real
    sizes from weight shapes, the platform gate reading TPU."""
    import collections

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import _text2image_jit
    from p2p_tpu.models import SD14, SD21, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.models.conditioning import zeros_for
    from p2p_tpu.models.config import SDXL, unet_layout
    from p2p_tpu.ops import schedulers as sched_mod
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    cfg = {"sd14": SD14, "sd21": SD21, "sdxl": SDXL}[name]
    window = {"sd14": {"self_max_pixels": 16 * 16},
              "sd21": {"self_max_pixels": 24 * 24}, "sdxl": {}}[name]
    tok = HashWordTokenizer(model_max_length=cfg.unet.context_len)
    ctrl = factory.attention_replace(
        ["a cat riding a bike", "a dog riding a bike"], 50, 0.8, 0.4, tok,
        max_len=cfg.unet.context_len, store=True, **window)
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl)
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    sched = sched_mod.schedule_from_config(50, cfg.scheduler, kind="ddim")
    side = cfg.latent_size
    cond = jax.eval_shape(lambda: zeros_for(cfg, 2))
    latents = jax.ShapeDtypeStruct((2, side, side, cfg.unet.in_channels),
                                   jnp.float32)
    launches.built()
    _text2image_jit.trace(unet, vae, cfg, layout, sched, "ddim", cond, cond,
                          latents, ctrl, jnp.float32(cfg.guidance_scale), None,
                          False)
    assert dict(collections.Counter(
        (s.rows, s.channels, s.inner, s.how)
        for s in launches._traced_ff.values())) == CELL_FF[name]


# ---------------------------------------------------------------------------
# Through the model: the tiny pipeline with the kernel engaged
# ---------------------------------------------------------------------------


@pytest.fixture
def kernel_on(monkeypatch):
    """The model as on the chip, the kernel executed by the interpreter: the
    platform gate reads TPU (the tiny preset's self sites are under the flash
    kernel's 1,024 keys, so nothing else changes), the tiny blocks get
    table rows of their own, and every Pallas call runs interpreted. The
    in-memory compile caches are emptied on both sides, so that no program traced with the
    formula answers for the kernel's, nor one with the kernel for a later
    test's."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    jax.clear_caches()
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    monkeypatch.setattr(nn, "_FF_BY_SHAPE", {(256, 32, 64): (64, 64),
                                             (64, 64, 128): (64, 64),
                                             (16, 64, 128): (16, 64)})
    with force_tpu_interpret_mode():
        yield
    jax.clear_caches()


def _kernel_blocks():
    """The feed-forwards the last traced program ran on the kernel."""
    return sum(s.how == "kernel" for s in launches._traced_ff.values())


def _text2image(pipe):
    from p2p_tpu.engine.sampler import text2image

    images, latents, _ = text2image(pipe, ["a cat riding a bike",
                                           "a dog riding a bike"], None,
                                    num_steps=2, rng=jax.random.PRNGKey(5))
    return np.asarray(images, np.float32), np.asarray(latents)


def test_text2image_through_the_kernel(tiny_pipe, kernel_on):
    images, latents = _text2image(tiny_pipe)
    assert _kernel_blocks() == 7
    with pytest.MonkeyPatch.context() as m:
        m.setattr(nn, "_on_tpu", lambda: False)
        jax.clear_caches()
        ref_images, ref_latents = _text2image(tiny_pipe)
        assert _kernel_blocks() == 0
    rel = np.linalg.norm(latents - ref_latents) / np.linalg.norm(ref_latents)
    assert rel < 1e-2, rel
    assert np.abs(images - ref_images).mean() < 1.0


def _formula_too(run):
    """``run()`` once more with the platform gate on the CPU's answer."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(nn, "_on_tpu", lambda: False)
        jax.clear_caches()
        out = run()
        assert _kernel_blocks() == 0
    return out


def test_null_text_inversion_through_the_kernel(tiny_pipe, kernel_on):
    """``engine/inversion.py`` differentiates the U-Net: through the kernel's
    ``custom_vjp``, whose backward is the formula's."""
    from p2p_tpu.engine.inversion import invert

    side = tiny_pipe.config.image_size
    image = np.random.default_rng(2).integers(0, 256, (side, side, 3), np.uint8)

    def run():
        art = invert(tiny_pipe, image, "a cat riding a bike", num_steps=2,
                     num_inner_steps=2)
        return np.asarray(art.uncond_embeddings)

    got = run()
    assert _kernel_blocks() == 7
    want = _formula_too(run)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-2


def test_sweep_through_the_kernel(tiny_pipe, kernel_on):
    """``parallel.sweep``: groups under ``vmap`` (the kernel batches over a
    grid axis) and over a ``dp`` mesh (``nn.kernel_mesh`` runs it per
    device)."""
    from p2p_tpu.engine.sampler import encode_prompts
    from p2p_tpu.parallel import make_mesh, seed_latents, sweep

    ctx = jnp.concatenate([encode_prompts(tiny_pipe, [""] * 2),
                           encode_prompts(tiny_pipe, ["a cat", "a dog"])])
    ctx_g = jnp.broadcast_to(ctx[None], (2,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(3), 2, 2, tiny_pipe.latent_shape)
    mesh = make_mesh(2, tp=1, devices=jax.devices()[:2])

    def run(mesh):
        images, _ = sweep(tiny_pipe, ctx_g, lats, None, num_steps=2, mesh=mesh)
        return np.asarray(images, np.float32)

    for m in (None, mesh):
        got = run(m)
        assert _kernel_blocks() == 7
        want = _formula_too(lambda: run(m))
        assert np.abs(got - want).mean() < 1.0
        jax.clear_caches()
