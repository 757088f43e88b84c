"""Ask the chip's compiler, without a chip: the Pallas kernels of the main
path compiled for a *described* TPU v5e (``v5e:2x2`` topology, nothing
attached) at SD-1.4 and SD-2.1 widths in bf16.

Interpret-mode tests (tests/test_kernels.py, tests/test_flash_pallas.py)
prove the kernels' arithmetic; they cannot see what Mosaic refuses — a
block that does not align to the tiling, a kernel over its scoped-VMEM
budget. These compiles can, at about a second each and no chip time:

(a) the fused edit kernel (``kernels.fused_edit.fused_site_attention``) for
    the replace, refine and reweight controllers at every distinct site
    geometry of ``unet_layout(SD14.unet).metas`` and of SD-2.1's 96x96
    latent the kernel covers (the 64x64 and 96x96 self sites are not fused
    by design and stay on flash; SD-2.1's 48x48 self site has no block that
    fits, ``nn.edit_block`` answers 0, and keeps the materialized path);
(b) ``nn.flash_attention_tpu`` forward and its ``jax.grad`` (null-text
    inversion differentiates through it) at every row of the geometry table
    ``nn.flash_block`` answers from (``FLASH_ROWS``): arrays in the row's
    dtype, the kernel handed bfloat16 in every row (f32 arrays are narrowed
    in front of it, PR 35) and tiled by the table's answer at 2 B;
(c) ``nn.flash_attention_residuals`` at the same rows and at the
    ring-attention chunks (``RING_CHUNKS``), operands as they come: the f32
    rows are the table's answers at 4 B, which only this variant still runs;
(d) both kernels inside a program partitioned over a ``dp`` mesh of the four
    described chips, the way ``parallel.sweep`` traces its groups
    (``nn.kernel_mesh`` + ``vmap(spmd_axis_name="dp")``) — the partitioner
    refuses a Mosaic kernel that is not wrapped per device, which is how
    ``serve --mesh dp=4`` first failed on four chips.

(e) the whole sampling program of the cell `sd21.edit-replace`
    (``engine.sampler._text2image_jit`` at SD-2.1's sizes, the cell's
    controller, no store taken back): the five 48x48 self sites whose store
    nobody reads are flash calls at (4, 10, 2304, 64) beside the five at
    9,216 keys, and no (4, 10, 2304, 2304) tensor exists (about two minutes).

(g) the whole sampling program of the cell `sdxl.edit-replace` (PR 36): ten
    flash calls at (4, 10, 4096, 64) in its loop, and sixty at (4, 20, 1024,
    64), the sites at 1,024 keys the controller only injects into (PR 37);
    two decode chunks, and arguments, temporaries and code within 15 GiB by
    XLA's own count (about three minutes).

(h) the GEGLU feed-forward kernel (``kernels.geglu``) alone at `sdxl`'s two
    block shapes, the ones ``nn.ff_block`` gives a tile, and at its 32²
    block inside a ``dp``-partitioned program; in (g) every transformer
    block is one such call and the program holds no f32 ``(tokens,
    2·inner)`` product of ``ff_in``; in (e) every block keeps the formula.

(f) two ResNet blocks of `sd14`'s first level (``unet._apply_resnet``, f32,
    320 wide at 64x64): GroupNorm's statistics leave the activation
    channels-minor, so no copy of it to a W-minor layout is compiled (PR 32).

Nothing runs, so nothing here says anything about results or times. The
topology is described inside a module-scoped fixture (never at import: the
TPU library belongs to one process, and every xdist worker imports this
file), in this process (a child could not load the library either), with
the persistent compilation cache off (a described-device executable cannot
be read back from it). All of these tests live in this one file so one
worker gets them all.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.align.words import get_equalizer
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.kernel_spec import kernel_edit_spec
from p2p_tpu.kernels.fused_edit import fused_site_attention
from p2p_tpu.models import SD14, SD21, nn
from p2p_tpu.models.config import unet_layout
from p2p_tpu.utils.tokenizer import HashWordTokenizer

STEPS = 50
CFG_BATCH = 4          # [uncond; uncond; base; edit] of a 2-prompt edit
MODES = ("replace", "refine", "reweight")


def _geometries():
    """One ``AttnMeta`` per distinct (cross?, pixels, d_head) of SD-1.4
    (heads of 40 / 80 / 160) and of SD-2.1 at 768x768 (heads of 64)."""
    seen = {}
    for cfg in (SD14, SD21):
        for m in unet_layout(cfg.unet).metas:
            seen.setdefault((m.is_cross, m.pixels, m.channels // m.heads), m)
    return seen


GEOMETRIES = _geometries()
#: The sites the fused kernel covers: every cross site, and the self sites
#: under 4096 pixels for which ``nn.edit_block`` has a query block.
FUSED = [key for key, m in GEOMETRIES.items()
         if key[0] or (key[1] < 4096 and nn.edit_block(key[1], m.key_len, key[2], 2))]


def _geom_id(key):
    cross, pixels, d_head = key
    return f"{'cross' if cross else 'self'}-P{pixels}-d{d_head}"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def controllers():
    tok = HashWordTokenizer(model_max_length=SD14.text.max_length)
    kw = dict(tokenizer=tok, max_len=SD14.text.max_length,
              self_max_pixels=32 * 32, store=False)
    base = "a cat riding a bike"
    eq = get_equalizer(base, ["cat"], [3.0], tok, mode="paired")
    return {
        "replace": factory.attention_replace(
            [base, "the dog eating some pizza"], STEPS, 0.8, 0.4, **kw),
        "refine": factory.attention_refine(
            [base, "a fluffy cat riding a red bike"], STEPS, 0.8, 0.4, **kw),
        "reweight": factory.attention_reweight(
            [base, base], STEPS, 0.8, 0.4, eq, **kw),
    }


def _shapes(tree, sharding):
    """``tree`` with every array replaced by its shape on the described
    device (there is no device to hold an array)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "the compiled program holds no Pallas kernel")
    return compiled


def _qkv(one_chip, batch, heads, pixels, key_len, d_head, dtype=jnp.bfloat16):
    def sds(n):
        return jax.ShapeDtypeStruct((batch, heads, n, d_head), dtype,
                                    sharding=one_chip)

    return sds(pixels), sds(key_len), sds(key_len)


def test_described_device_is_the_v5e_the_peaks_table_knows(topo):
    from p2p_tpu.obs import costmodel

    dev = topo.devices[0]
    assert dev.platform == "tpu"
    assert costmodel.lookup_peaks(dev.device_kind) is \
        costmodel.PLATFORM_PEAKS["v5 lite"]


def test_fused_geometries_cover_the_layout():
    # cross P in {4096, 1024, 256, 64}, self P in {1024, 256, 64}; the
    # 64x64 self site is the only one left to flash.
    sd14 = [k for k in FUSED if k[2] != 64]
    assert sorted(k[1] for k in sd14 if k[0]) == [64, 256, 1024, 4096]
    assert sorted(k[1] for k in sd14 if not k[0]) == [64, 256, 1024]
    # SD-2.1, 96x96 latent: cross P in {9216, 2304, 576, 144}, self P in
    # {576, 144}; flash has the 96x96 self site, and the 48x48 one, whose
    # 2304 x 2304 edit transform alone is over the kernel's VMEM, stays
    # materialized when a controller edits it (ROADMAP Reach 3).
    sd21 = [k for k in FUSED if k[2] == 64]
    assert sorted(k[1] for k in sd21 if k[0]) == [144, 576, 2304, 9216]
    assert sorted(k[1] for k in sd21 if not k[0]) == [144, 576]
    assert [k for k in GEOMETRIES if k not in FUSED] == [
        (False, 4096, 40), (False, 9216, 64), (False, 2304, 64)]


@pytest.mark.parametrize("key", FUSED, ids=_geom_id)
@pytest.mark.parametrize("mode", MODES)
def test_fused_edit_kernel_compiles(one_chip, controllers, mode, key):
    meta = GEOMETRIES[key]
    ctrl = controllers[mode]
    d_head = key[2]
    assert kernel_edit_spec(ctrl, meta) is not None
    q, k, v = _qkv(one_chip, CFG_BATCH, meta.heads, meta.pixels,
                   meta.key_len, d_head)

    def site(ctrl, q, k, v, step):
        out = fused_site_attention(q, k, v, d_head ** -0.5, ctrl, meta, step)
        assert out is not None, "the kernel declined this site"
        return out

    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(site, _shapes(ctrl, one_chip), q, k, v, step)


#: (pixels, heads, d_head, dtype): the shapes that take the library flash
#: kernel — SD-1.4's 64x64 and 32x32 self sites as the benchmark runs them
#: (f32) and as ``sweep(dtype=bfloat16)`` does, LDM-256's 32x32 site, the
#: VAE decoder's mid attention (one 512-wide head, f32), SD14_HR's 128x128,
#: SD-2.x's 64x64 site (head size 64), and SD-2.1 at 768x768 (PR 29): its
#: 96x96 and 48x48 self sites (9216 and 2304 keys, 9 x 2^n: the backward
#: blocks have to tile them too) and the VAE's mid attention at 96x96; the
#: two SD-2.1 self sites in bf16 as well (PR 35), so that every length with
#: a row of its own in the table is asked at both widths; and a 256-wide
#: bf16 head at 2,304 keys, which no preset has: the one other answer that
#: the VMEM budget's move to 14.75 MiB changed (PERF.md §6, PR 35); and SDXL
#: at 1024x1024 (PR 36): its 64x64 and 32x32 self sites (10 and 20 heads of
#: 64) and the VAE's mid attention at 128x128.
FLASH_ROWS = [(4096, 8, 40, jnp.float32), (1024, 8, 80, jnp.float32),
              (4096, 8, 40, jnp.bfloat16), (1024, 8, 80, jnp.bfloat16),
              (1024, 5, 64, jnp.float32), (4096, 1, 512, jnp.float32),
              (16384, 8, 40, jnp.float32), (4096, 5, 64, jnp.float32),
              (9216, 5, 64, jnp.float32), (2304, 10, 64, jnp.float32),
              (9216, 1, 512, jnp.float32),
              (9216, 5, 64, jnp.bfloat16), (2304, 10, 64, jnp.bfloat16),
              (2304, 2, 256, jnp.bfloat16),
              (4096, 10, 64, jnp.float32), (1024, 20, 64, jnp.float32),
              (16384, 1, 512, jnp.float32)]
#: PixArt-Sigma at 1024x1024: its 28 self sites, 16 heads of 72 at 64x64
#: tokens, forward only (no backward or ring path runs a transformer
#: denoiser).
DIT_ROW = (4096, 16, 72, jnp.float32)
#: The 64x64 self site in bf16, which the mesh cases run, and its local chunks
#: under parallel/ring.py at sp = 2 and 4 (the residuals kernel runs on those).
FLASH_SITE = FLASH_ROWS[2]
RING_CHUNKS = [(4096 // sp,) + FLASH_SITE[1:] for sp in (2, 4)]


def _row_id(row):
    pixels, heads, d_head, dtype = row
    return f"P{pixels}-h{heads}-d{d_head}-{jnp.dtype(dtype).name}"


def _row(one_chip, row, batch=CFG_BATCH, residuals=False):
    """``(geometry, scale, (q, k, v))`` of a table row on the described chip.
    The geometry is the table's answer at the width the kernel is handed the
    row's arrays in: ``nn.flash_operand_dtype`` of theirs through
    ``flash_attention_tpu``, their own through the residuals variant."""
    pixels, heads, d_head, dtype = row
    handed = jnp.dtype(dtype) if residuals else nn.flash_operand_dtype(dtype)
    geometry = nn.flash_block(pixels, d_head, handed.itemsize)
    assert geometry is not None, "the table has no geometry for this row"
    return geometry, d_head ** -0.5, _qkv(one_chip, batch, heads, pixels,
                                          pixels, d_head, dtype)


@pytest.mark.parametrize("row", FLASH_ROWS + [DIT_ROW], ids=_row_id)
def test_flash_forward_compiles(one_chip, row):
    geometry, scale, qkv = _row(one_chip, row)
    compiled = _compile(
        lambda q, k, v: nn.flash_attention_tpu(q, k, v, scale, geometry), *qkv)
    # f32 arrays or bf16: the kernel takes bfloat16 and writes bfloat16, and
    # the caller gets its own dtype back.
    kernel, = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    shapes = kernel.split("custom_call_target")[0]    # result and operands
    assert " = bf16[" in shapes and "f32[" not in shapes
    out, = jax.tree.leaves(compiled.out_info)
    assert out.dtype == row[3]


@pytest.mark.parametrize("row", FLASH_ROWS, ids=_row_id)
def test_flash_backward_compiles(one_chip, row):
    # Null-text inversion backpropagates through the flash sites: every
    # backward block of nn._flash_block_sizes has to be one Mosaic accepts.
    geometry, scale, qkv = _row(one_chip, row, batch=2)

    def loss(q, k, v):
        out = nn.flash_attention_tpu(q, k, v, scale, geometry)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)


@pytest.mark.parametrize("row", FLASH_ROWS + RING_CHUNKS, ids=_row_id)
def test_flash_residuals_compile(one_chip, row):
    geometry, scale, (q, k, v) = _row(one_chip, row, residuals=True)
    compiled = _compile(
        lambda q, k, v: nn.flash_attention_residuals(q, k, v, scale, geometry),
        q, k, v)
    out, l, m = compiled.out_info
    assert out.shape == q.shape and l.shape == m.shape == q.shape[:3]


@pytest.fixture(scope="module")
def dp_mesh(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("dp",))


def _dp_groups(dp_mesh, *shapes):
    """One group per device: ``shapes`` with a leading, dp-sharded axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    grp = NamedSharding(dp_mesh, P("dp"))
    n = dp_mesh.devices.size
    return [jax.ShapeDtypeStruct((n,) + s.shape, s.dtype, sharding=grp)
            for s in shapes]


def _groups(one_group, mesh):
    """``parallel.sweep._vmap_groups``: the program's own way of running
    groups under a mesh."""
    from p2p_tpu.parallel.sweep import _vmap_groups

    return _vmap_groups(one_group, mesh)


def test_flash_kernel_compiles_under_a_dp_mesh(topo, dp_mesh):
    geometry, scale, single = _row(None, FLASH_SITE)
    q, k, v = _dp_groups(dp_mesh, *single)

    def one_group(q, k, v):
        return nn.flash_attention_tpu(q, k, v, scale, geometry)

    compiled = _compile(_groups(one_group, dp_mesh), q, k, v)
    assert "all-gather" not in compiled.as_text()
    # Without the per-device wrapping the partitioner refuses the kernel.
    with pytest.raises(NotImplementedError, match="Mosaic"):
        jax.jit(jax.vmap(one_group)).lower(q, k, v)


def test_fused_edit_kernel_compiles_under_a_dp_mesh(topo, dp_mesh,
                                                    controllers):
    key = (True, 4096, 40)
    meta, ctrl = GEOMETRIES[key], controllers["replace"]
    single = _qkv(None, CFG_BATCH, meta.heads, meta.pixels, meta.key_len,
                  key[2])
    q, k, v = _dp_groups(dp_mesh, *single)
    ctrl_g = jax.tree.map(lambda a: _dp_groups(dp_mesh, a)[0], ctrl)
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(dp_mesh, P()))

    def program(ctrl_g, q, k, v, step):
        def one_group(ctrl, q, k, v):
            return fused_site_attention(q, k, v, key[2] ** -0.5, ctrl, meta,
                                        step)

        return _groups(one_group, dp_mesh)(ctrl_g, q, k, v)

    _compile(program, ctrl_g, q, k, v, step)


#: (rows, channels, inner, kernel dtype): `sdxl`'s two transformer block
#: shapes, the ones the tile table gives the kernel (kernels stored in
#: bfloat16), each compiled alone.
FF_KERNEL_BLOCKS = [(16384, 640, 2560, jnp.bfloat16),
                    (4096, 1280, 5120, jnp.bfloat16)]


def _ff_id(block):
    rows, channels, inner, dtype = block
    return f"{rows}x{channels}x{inner}-{jnp.dtype(dtype).name}"


def _ff_args(sharding, rows, channels, inner, kernel_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    x = sds((CFG_BATCH, rows // CFG_BATCH, channels), jnp.float32)
    p_in = {"kernel": sds((channels, 2 * inner), kernel_dtype),
            "bias": sds((2 * inner,), jnp.float32)}
    p_out = {"kernel": sds((inner, channels), kernel_dtype),
             "bias": sds((channels,), jnp.float32)}
    return x, p_in, p_out


@pytest.mark.parametrize("block", FF_KERNEL_BLOCKS, ids=_ff_id)
def test_geglu_kernel_compiles(one_chip, block):
    from p2p_tpu.kernels import geglu

    tile = nn.ff_block(block[0] // CFG_BATCH, *block[1:3], 2)
    x, p_in, p_out = _ff_args(one_chip, *block)
    compiled = _compile(lambda x, n, a, b: geglu.geglu_feed_forward(
        x, n, a, b, tile), x, x, p_in, p_out)
    text = compiled.as_text()
    kernel, = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # the normed rows reach the kernel in bfloat16, the output leaves in f32,
    # and no (rows, 2·inner) or (rows, inner) tensor exists outside it
    assert "bf16[%d,%d]" % (block[0], block[1]) in kernel
    assert "f32[%d,%d]" % (block[0], 2 * block[2]) not in text
    assert "[%d,%d]" % (block[0], block[2]) not in text


def test_geglu_kernel_compiles_under_a_dp_mesh(topo, dp_mesh, monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_tpu.kernels import geglu

    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    block = (4096, 1280, 5120, jnp.bfloat16)       # `sdxl`'s 32² block
    tile = nn.ff_block(block[0] // CFG_BATCH, *block[1:3], 2)
    x, p_in, p_out = _ff_args(NamedSharding(dp_mesh, P()), *block)
    xg, = _dp_groups(dp_mesh, x)

    def program(xg, p_in, p_out):
        def one_group(x):
            how, _ = geglu.plan(x, p_in, p_out)
            assert how == "kernel"
            return geglu.geglu_feed_forward(x, x, p_in, p_out, tile)

        return _groups(one_group, dp_mesh)(xg)

    assert "all-gather" not in _compile(program, xg, p_in, p_out).as_text()


def test_sd21_cell_program_runs_its_store_only_sites_on_the_kernel(one_chip,
                                                                   monkeypatch):
    """`sd21.edit-replace` sends ``store=True`` and takes no store back, so
    the store has no reader and no slot (``AttnLayout.for_readers``): the five
    48x48 self sites above the 24x24 edit window are untouched and reach
    ``nn.fused_attention``, which gives 2,304 keys the geometry (768, 2304,
    1152). The compiled program then has ten flash calls in its loop and
    neither the probabilities (4, 10, 2304, 2304) nor the store's
    (2, 10, 2304, 2304) anywhere."""
    import re

    from p2p_tpu.engine.sampler import _text2image_jit
    from p2p_tpu.models import init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.obs import launches
    from p2p_tpu.ops import schedulers as sched_mod

    # the program asks the backend, which is the CPU's here, not the device
    # it is compiled for
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    cfg = SD21
    tok = HashWordTokenizer(vocab_size=cfg.text.vocab_size,
                            model_max_length=cfg.text.max_length)
    ctrl = factory.attention_replace(
        ["a cat riding a bike", "a dog riding a bike"], STEPS, 0.8, 0.4, tok,
        self_max_pixels=24 * 24, max_len=cfg.text.max_length, store=True)
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl)          # as ``text2image`` does
    assert layout.num_store_slots == 0
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    sched = sched_mod.schedule_from_config(STEPS, cfg.scheduler, kind="ddim")
    side = cfg.latent_size
    launches.built()                           # the blocks noted from here on
    ctx = jnp.zeros((2, cfg.unet.context_len, cfg.unet.context_dim))
    args = _shapes((unet, vae, sched, ctx, ctx,
                    jnp.zeros((2, side, side, cfg.unet.in_channels)), ctrl,
                    jnp.float32(7.5)), one_chip)
    unet, vae, sched, cond, uncond, latents, ctrl, scale = args
    text = _text2image_jit.lower(
        unet, vae, cfg, layout, sched, "ddim", cond, uncond, latents, ctrl,
        scale, None, False).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # f32 arrays at the default matmul precision: the kernel's operands and
    # its output are bfloat16 (PR 35)
    by_shape = {shape: sum(1 for line in kernels
                           if re.search(r"= bf16\[%s\]" % shape, line))
                for shape in ("4,10,2304,64", "4,5,9216,64", "2,1,9216,512")}
    # ten self sites in the loop, and the VAE's mid attention outside it
    assert by_shape == {"4,10,2304,64": 5, "4,5,9216,64": 5, "2,1,9216,512": 1}
    # the tile table keeps every transformer block's feed-forward on XLA's
    # formula here
    assert _ff_counts() == {(36864, 320, 1280, "formula"): 5,
                            (9216, 640, 2560, "formula"): 5,
                            (2304, 1280, 5120, "formula"): 5,
                            (576, 1280, 5120, "formula"): 1}
    assert len(kernels) == 11
    assert "f32[4,10,2304,2304]" not in text
    assert "f32[2,10,2304,2304]" not in text


def _ff_counts():
    """The traced program's feed-forwards: ``{(rows, channels, inner, how):
    blocks}`` (``launches.note_ff_site``)."""
    import collections

    from p2p_tpu.obs import launches

    return dict(collections.Counter((s.rows, s.channels, s.inner, s.how)
                                    for s in launches._traced_ff.values()))


def test_sdxl_cell_program_compiles_and_fits_one_chip(one_chip, monkeypatch):
    """`sdxl.edit-replace`'s sampling program for the described v5e: kernels
    arrive in bfloat16 (4.9 GiB of arguments for the U-Net and the
    autoencoder; the towers are another program's), the ten 64x64 self sites
    are flash calls, the sixty 32x32 sites are the controller's, which only
    injects into them, so they are flash calls too, on the base row's q and
    k in the edit rows (ISSUE 37), and no (4, 20, 1024, 1024) probabilities
    exist; the decode is two chunks of one image with its mid attention on
    the kernel, and nothing in it is a second ``while``. By ``memory_analysis()`` the program's arguments, temporaries
    and code come to under 15 GiB of the chip's 16: the widening of a kernel
    stays at its use and is not hoisted into float32 copies of the tree."""
    import re

    from p2p_tpu.engine.sampler import _text2image_jit
    from p2p_tpu.models import init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.models.conditioning import zeros_for
    from p2p_tpu.models.config import SDXL
    from p2p_tpu.obs import launches
    from p2p_tpu.ops import schedulers as sched_mod

    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    cfg = SDXL
    tok = HashWordTokenizer(model_max_length=cfg.unet.context_len)
    ctrl = factory.attention_replace(
        ["a cat riding a bike", "a dog riding a bike"], STEPS, 0.8, 0.4, tok,
        max_len=cfg.unet.context_len, store=True)
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)                # the self window from the model: 32²
    assert ctrl.edit.self_max_pixels == 32 * 32
    layout = layout.for_readers(ctrl)
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    sched = sched_mod.schedule_from_config(STEPS, cfg.scheduler, kind="ddim")
    side = cfg.latent_size
    cond = zeros_for(cfg, 2)
    args = _shapes((unet, vae, sched, cond, cond,
                    jnp.zeros((2, side, side, cfg.unet.in_channels)), ctrl,
                    jnp.float32(cfg.guidance_scale)), one_chip)
    unet, vae, sched, cond, uncond, latents, ctrl, scale = args
    launches.built()                           # the sites noted from here on
    compiled = _text2image_jit.lower(
        unet, vae, cfg, layout, sched, "ddim", cond, uncond, latents, ctrl,
        scale, None, False).compile()
    hows = launches._traced_sites
    assert sorted((s.keys, s.how) for s in hows.values()) == \
        [(1024, "edited")] * 60 + [(4096, "kernel")] * 10
    assert {s.geometry for s in hows.values() if s.how == "kernel"} == {(256, 4096, 2048)}
    assert {(s.geometry, s.operand) for s in hows.values() if s.how == "edited"} == {
        ((1024, 1024, 1024), "bfloat16")}
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    by_shape = {shape: sum(1 for line in kernels
                           if re.search(r"= bf16\[%s\]" % shape, line))
                for shape in ("4,20,1024,64", "4,10,4096,64", "1,1,16384,512")}
    assert by_shape == {"4,20,1024,64": 60, "4,10,4096,64": 10, "1,1,16384,512": 2}
    # every transformer block's feed-forward is one GEGLU kernel call, and the
    # f32 (tokens, 2·inner) product between XLA's two fusions is gone
    assert _ff_counts() == {(4096, 1280, 5120, "kernel"): 60,
                            (16384, 640, 2560, "kernel"): 10}
    assert len([line for line in kernels if "geglu_feed_forward" in line]) == 70
    assert len(kernels) == 142
    assert "f32[4,20,1024,1024]" not in text and "f32[4,10,4096,4096]" not in text
    assert "f32[4,1024,10240]" not in text and "f32[4,4096,5120]" not in text
    assert len(re.findall(r" while\(", text)) == 1
    stats = compiled.memory_analysis()
    gib = 2.0 ** 30
    assert 4.7 < stats.argument_size_in_bytes / gib < 5.1
    assert (stats.argument_size_in_bytes + stats.temp_size_in_bytes
            + stats.generated_code_size_in_bytes) / gib <= 15.0
    # one image at a time: two images at once would hold 1 GiB at every
    # full-size activation (PERF.md §6, PR 36 has XLA's count of both)
    assert stats.temp_size_in_bytes / gib < 4.5


def test_resnet_blocks_keep_the_activation_channels_minor(one_chip):
    """Two ``unet._apply_resnet`` blocks 320 -> 320 -> 320 on f32[4,64,64,320]
    (`sd14`'s ``down0``): GroupNorm takes its moments per channel and combines
    a group's channels on the (N, C) vector, so no (N, H, W, 32, 10) view of
    the activation is reduced and XLA copies the activation to no layout whose
    minor dimension is W (``{2,1,3,0}``), which PR 31's tree did four times
    (PERF.md §6, PR 32; `tools/hlo_costs.py` lists them)."""
    import re

    from p2p_tpu.models import unet

    key = jax.random.PRNGKey(0)
    blocks = [jax.eval_shape(lambda: unet._resnet_init(key, 320, 320, 1280))
              for _ in range(2)]

    def chain(blocks, x, temb):
        for i, p in enumerate(blocks):
            with jax.named_scope(f"unet/down0/res{i}"):
                x = unet._apply_resnet(p, x, temb, 32)
        return x

    x = jax.ShapeDtypeStruct((4, 64, 64, 320), jnp.float32, sharding=one_chip)
    temb = jax.ShapeDtypeStruct((4, 1280), jnp.float32, sharding=one_chip)
    text = jax.jit(chain).lower(_shapes(blocks, one_chip), x, temb
                                ).compile().as_text()
    assert text.count(" convolution(") >= 4
    w_minor = [line.split(" = ")[0].strip() for line in text.splitlines()
               if re.search(r"= f32\[4,64,64,[0-9,]+\]\{2,[0-9,]*[:}].* copy\(",
                            line)]
    assert w_minor == []
    assert "f32[4,64,64,32,10]" not in text
