"""Scopes through the whole compiled step and the index that reads them back
(ISSUE 27): ``jax.named_scope``s in the models and the sampler's scan bodies,
``obs.traceparse.scope_index`` on the compiled program's text, and the launch
registry ``obs.launches`` that offers the index of a program that ran.

All on the CPU at the tiny preset: the names, the coverage and the laziness
are the program's; which instructions the TPU's compiler makes of it is read
on the chip (PERF.md)."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.controllers import factory
from p2p_tpu.engine import sampler
from p2p_tpu.engine.sampler import encode_prompts, phase2_controller, text2image
from p2p_tpu.kernels.dispatch import site_name
from p2p_tpu.models import TINY
from p2p_tpu.models.config import unet_layout
from p2p_tpu.obs import launches, traceparse
from p2p_tpu.parallel.sweep import (seed_latents, sweep, sweep_phase1,
                                    sweep_phase2)
from p2p_tpu.utils.cache import compile_ledger

PROMPTS = ["a cat on a mat", "a dog on a mat"]
STEPS = 3          # no other test samples 3 steps: these programs are new here
GATE = 2


def _ctrl(pipe, store=True):
    return factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, pipe.tokenizer, self_max_pixels=16 * 16,
        max_len=TINY.text.max_length, store=store)


def _sweep_inputs(pipe):
    ctrls = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (1,) + x.shape),
                                   _ctrl(pipe, store=False))
    cond = encode_prompts(pipe, PROMPTS)
    uncond = encode_prompts(pipe, [""] * len(PROMPTS))
    ctx = jnp.concatenate([uncond, cond], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(42), 1, len(PROMPTS), pipe.latent_shape)
    return ctx, lats, ctrls


def _launch_text2image(pipe):
    text2image(pipe, PROMPTS, _ctrl(pipe), num_steps=STEPS)


def _launch_encode(pipe):
    encode_prompts(pipe, PROMPTS + ["a third prompt makes a batch of three"])


def _launch_sweep(pipe):
    ctx, lats, ctrls = _sweep_inputs(pipe)
    sweep(pipe, ctx, lats, ctrls, num_steps=STEPS)


def _launch_mesh_sweep(pipe):
    """Four groups over a dp=4 mesh: the arguments are committed to it, and
    the registry has to keep those shardings to lower the same program."""
    from p2p_tpu.parallel import make_mesh

    ctx, lats, ctrls = _sweep_inputs(pipe)
    four = lambda x: jnp.broadcast_to(x[0], (4,) + x.shape[1:])     # noqa: E731
    lats = seed_latents(jax.random.PRNGKey(42), 4, len(PROMPTS), pipe.latent_shape)
    sweep(pipe, four(ctx), lats, jax.tree_util.tree_map(four, ctrls),
          num_steps=STEPS, mesh=make_mesh(4, tp=1, devices=jax.devices("cpu")[:4]))


def _launch_phases(pipe):
    ctx, lats, ctrls = _sweep_inputs(pipe)
    carry = sweep_phase1(pipe, ctx, lats, ctrls, num_steps=STEPS, gate=GATE)
    two = phase2_controller(_ctrl(pipe, store=False))
    if two is not None:
        two = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (1,) + x.shape), two)
    sweep_phase2(pipe, ctx[:, len(PROMPTS):], carry, two, num_steps=STEPS, gate=GATE)


LAUNCH_SITES = {
    "text2image": ("jit__text2image_jit", _launch_text2image, "unet/mid0/res0"),
    "encode": ("jit__encode_jit", _launch_encode, "text_encoder"),
    "sweep": ("jit__sweep_jit", _launch_sweep, "vae.decode/mid"),
    "sweep_dp4": ("jit__sweep_jit", _launch_mesh_sweep, "unet/up1/upsample"),
    "sweep_phase1": ("jit__sweep_phase1_jit", _launch_phases, "sampler/cfg"),
    "sweep_phase2": ("jit__sweep_phase2_jit", _launch_phases, "sampler/scheduler_step"),
}


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """JAX leaves metadata out of the compilation cache's key, so the suite's
    ``.jax_cache`` may serve these programs as a tree with other scopes
    compiled them (PERF.md). What is asserted here is this tree's scopes:
    every program of this module is compiled, none read."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def text2image_hlo(tiny_pipe):
    """The compiled tiny ``_text2image_jit`` as the registry lowers it again."""
    _launch_text2image(tiny_pipe)
    launch = launches.programs("jit__text2image_jit")[-1]
    return launch.fn.lower(*launch.args, **launch.kwargs).compile().as_text()


# -- (a) the index of a compiled program -----------------------------------


def test_index_names_every_site_resnet_block_and_the_decoder(text2image_hlo):
    index, _ = traceparse.scope_index(text2image_hlo)
    scopes = set(index.values())
    for meta in unet_layout(TINY.unet).metas:
        site = site_name(meta)
        assert traceparse.SITE_RE.fullmatch(site)
        for part in ("qkv", "core", "out"):
            assert any(s.endswith(f"/{site}/{part}") for s in scopes), (site, part)
    cfg = TINY.unet
    n = len(cfg.block_channels)
    want = {f"unet/down{k}/res{i}" for k in range(n) for i in range(cfg.layers_per_block)}
    want |= {f"unet/up{k}/res{i}" for k in range(n) for i in range(cfg.layers_per_block + 1)}
    want |= {"unet/mid0/res0", "unet/mid0/res1", "unet/conv_in", "unet/conv_out",
             "unet/time_embed", "sampler/cfg", "sampler/scheduler_step"}
    want |= {f"vae.decode/{part}" for part in ("conv_in", "mid", "up0", "conv_out")}
    assert want <= scopes
    # the attention-site form is the same function with SITE_RE applied
    sites = set(traceparse.op_site_index(text2image_hlo).values())
    assert sites == {site_name(m) for m in unet_layout(TINY.unet).metas}


def test_index_covers_the_instructions_that_can_run(text2image_hlo):
    index, mixed = traceparse.scope_index(text2image_hlo)
    # a trace shows the instructions of the entry and of the loops: not a
    # fusion's members nor a reduction's adder, and never a parameter, tuple
    # or constant
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text2image_hlo))
    current, total, covered = None, 0, 0
    for line in text2image_hlo.splitlines():
        im = traceparse._INSTR_RE.match(line)
        if im is None:
            cm = traceparse._COMP_RE.match(line)
            current = cm.group(1) if cm else current
            continue
        name, opcode = im.groups()
        if current in inner or opcode in ("parameter", "tuple", "constant",
                                          "get-tuple-element"):
            continue
        total += 1
        covered += name in index
    assert total > 500 and covered / total >= 0.95, (covered, total)
    assert mixed and all(len(m) > 1 for m in mixed.values())


class _ScopeSpy:
    """Every nested ``jax.named_scope`` path opened while tracing."""

    def __init__(self):
        self.stack, self.paths = [], set()

    @contextlib.contextmanager
    def __call__(self, name):
        self.stack.append(name)
        self.paths.add("/".join(self.stack))
        try:
            yield
        finally:
            self.stack.pop()


def _traceable(pipe):
    """The program behind ``text2image`` as a plain function of its arrays
    (a fresh one each time: no trace is cached across the two tracings)."""
    cfg, layout = pipe.config, unet_layout(pipe.config.unet)
    from p2p_tpu.ops import schedulers

    tsched = schedulers.schedule_from_config(STEPS, cfg.scheduler, kind="ddim")
    ctrl = _ctrl(pipe)
    ctx = jnp.zeros((2, cfg.unet.context_len, cfg.unet.context_dim))
    lat = jnp.zeros((2,) + pipe.latent_shape)

    def program(unet_params, vae_params, ctx_c, ctx_u, lat, ctrl, gs):
        return sampler._text2image_jit.__wrapped__(
            unet_params, vae_params, cfg, layout, tsched, "ddim", ctx_c, ctx_u,
            lat, ctrl, gs, None, False)

    return program, (pipe.unet_params, pipe.vae_params, ctx, ctx, lat, ctrl,
                     jnp.float32(7.5))


def test_only_the_documented_vocabulary_and_the_same_program(tiny_pipe, monkeypatch):
    spy = _ScopeSpy()
    monkeypatch.setattr(jax, "named_scope", spy)
    program, args = _traceable(tiny_pipe)
    scoped_jaxpr = str(jax.make_jaxpr(program)(*args))
    assert len(spy.paths) > 80
    undocumented = [p for p in spy.paths if not traceparse.SCOPE_RE.fullmatch(p)]
    assert not undocumented, undocumented
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    program, args = _traceable(tiny_pipe)
    assert str(jax.make_jaxpr(program)(*args)) == scoped_jaxpr
    bare = jax.jit(program).lower(*args).as_text()
    monkeypatch.undo()
    program, args = _traceable(tiny_pipe)
    assert jax.jit(program).lower(*args).as_text() == bare


def test_images_are_the_same_without_scopes(tiny_pipe, monkeypatch):
    kw = dict(num_steps=STEPS, rng=jax.random.PRNGKey(3))
    with_scopes = np.asarray(text2image(tiny_pipe, PROMPTS, _ctrl(tiny_pipe), **kw)[0])
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        without = np.asarray(text2image(tiny_pipe, PROMPTS, _ctrl(tiny_pipe), **kw)[0])
    finally:
        jax.clear_caches()         # nobody else gets the trace without scopes
    assert np.array_equal(with_scopes, without)


def test_scope_of_strips_what_jax_wraps_around_a_scope():
    assert traceparse.scope_of(
        "jit(f)/vmap(unet)/down0/while/body/closed_call/res0/sin") == "unet/down0/res0"
    assert traceparse.scope_of(
        "jit(_sweep_jit)/vmap(vae.decode)/up1/conv_general_dilated") == "vae.decode/up1"
    assert traceparse.scope_of("jit(f)/unet/down0/attn1/self_attn/down2/core/"
                               "jit(flash_attention)/pallas_call") \
        == "unet/down0/attn1/self_attn/down2/core"
    assert traceparse.scope_of("jit(f)/while/body/dynamic_slice") is None
    assert traceparse.scope_of("jit(f)/cross_attn/up7/q", traceparse.SITE_RE) \
        == "cross_attn/up7"


_TPU_HLO = """\
%fused_conv (p0: f32[2,8]{1,0:T(8,128)}, p1: bf16[8,8]{1,0:T(8,128)(2,1)S(1)}) -> f32[2,8]{1,0:T(8,128)} {
  %mul.1 = f32[2,8]{1,0:T(8,128)} multiply(%p0, %p0), metadata={op_name="jit(f)/while/body/closed_call/sampler/cfg/mul"}
  ROOT %conv.2 = f32[2,8]{1,0:T(8,128)} convolution(%mul.1, %p1), metadata={op_name="jit(f)/while/body/closed_call/unet/conv_in/conv_general_dilated"}
}
%body (t: (f32[2,8], bf16[8,8])) -> (f32[2,8], bf16[8,8]) {
  %gte.0 = f32[2,8]{1,0} get-tuple-element(%t), index=0
  %gte.1 = bf16[8,8]{1,0} get-tuple-element(%t), index=1
  %copy-start.4 = (bf16[8,8]{1,0}, bf16[8,8]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%gte.1)
  %copy-done.4 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.4)
  %fusion.9 = f32[2,8]{1,0:T(8,128)} fusion(%gte.0, %copy-done.4), kind=kOutput, calls=%fused_conv
  %copy.7 = f32[2,8]{1,0} copy(%fusion.9)
  ROOT %tuple.8 = (f32[2,8], bf16[8,8]) tuple(%copy.7, %gte.1)
}
"""


def test_fusion_ties_async_copies_and_straddling_on_tpu_shaped_text():
    index, mixed = traceparse.scope_index(_TPU_HLO)
    # one member each: the tie goes to the scope that owns the convolution
    assert index["fusion.9"] == "unet/conv_in"
    assert mixed == {"fusion.9": {"sampler/cfg": 1, "unet/conv_in": 1}}
    # the weights' prefetch has no metadata: it is its reader's
    assert index["copy-start.4"] == index["copy-done.4"] == "unet/conv_in"
    # not through the tuple, which reads everything
    assert "copy.7" not in index and "gte.1" not in index


_A = "jit(f)/while/body/closed_call/unet/down0/attn0"
_SITE_HLO = f"""\
%fused_proj_in (p0: f32[2,8]{{1,0:T(8,128)}}, p1: bf16[8,8]{{1,0:T(8,128)(2,1)}}) -> f32[2,8]{{1,0:T(8,128)}} {{
  %norm.1 = f32[2,8]{{1,0:T(8,128)}} multiply(%p0, %p0), metadata={{op_name="{_A}/proj_in/mul"}}
  ROOT %dot.2 = f32[2,8]{{1,0:T(8,128)}} dot(%norm.1, %p1), metadata={{op_name="{_A}/proj_in/dot_general"}}
}}
%fused_qkv (p0: f32[2,8]{{1,0:T(8,128)}}, p1: f32[2,8]{{1,0:T(8,128)}}) -> f32[2,8]{{1,0:T(8,128)}} {{
  %add.3 = f32[2,8]{{1,0:T(8,128)}} add(%p0, %p1), metadata={{op_name="{_A}/cross_attn/down0/qkv/add"}}
  %mul.4 = f32[2,8]{{1,0:T(8,128)}} multiply(%add.3, %p1), metadata={{op_name="{_A}/self_attn/down0/qkv/mul"}}
  ROOT %dot.5 = f32[2,8]{{1,0:T(8,128)}} dot(%mul.4, %p1), metadata={{op_name="{_A}/self_attn/down0/qkv/dot_general"}}
}}
%body (t: (f32[2,8], bf16[8,8])) -> (f32[2,8], bf16[8,8]) {{
  %gte.0 = f32[2,8]{{1,0}} get-tuple-element(%t), index=0
  %gte.1 = bf16[8,8]{{1,0}} get-tuple-element(%t), index=1
  %copy-start.6 = (bf16[8,8]{{1,0}}, bf16[8,8]{{1,0:S(1)}}, u32[]{{:S(2)}}) copy-start(%gte.1)
  %copy-done.6 = bf16[8,8]{{1,0:T(8,128)(2,1)S(1)}} copy-done(%copy-start.6)
  %fusion.7 = f32[2,8]{{1,0:T(8,128)}} fusion(%gte.0, %copy-done.6), kind=kOutput, calls=%fused_proj_in, metadata={{op_name="{_A}/proj_in/dot_general"}}
  %add.8 = f32[2,8]{{1,0:T(8,128)}} add(%gte.0, %gte.0), metadata={{op_name="jit(f)/while/body/closed_call/unet/down0/res1/add"}}
  %copy.9 = f32[2,8]{{1,0:T(8,128)}} copy(%add.8)
  %fusion.10 = f32[2,8]{{1,0:T(8,128)}} fusion(%fusion.7, %copy.9), kind=kOutput, calls=%fused_qkv, metadata={{op_name="{_A}/cross_attn/down0/qkv/add"}}
  ROOT %tuple.11 = (f32[2,8], bf16[8,8]) tuple(%fusion.10, %gte.1)
}}
"""


def test_the_site_index_leaves_out_what_is_outside_every_site():
    """``op_site_index`` feeds ``prodscope``'s per-site ledger: a ``proj_in``
    fusion or a ResNet block's add is in no site's time although a site's
    fusion reads it, and a weight's copy in front of ``proj_in`` is not
    carried through it to the site behind."""
    sites = traceparse.op_site_index(_SITE_HLO)
    # by members (2 of 3), not by the one member the fusion's metadata names
    assert sites == {"add.3": "cross_attn/down0", "mul.4": "self_attn/down0",
                     "dot.5": "self_attn/down0", "fusion.10": "self_attn/down0",
                     "copy.9": "self_attn/down0"}    # no metadata: its reader's
    index, mixed = traceparse.scope_index(_SITE_HLO)
    assert index["fusion.7"] == index["copy-done.6"] == "unet/down0/attn0/proj_in"
    assert index["add.8"] == "unet/down0/res1"
    assert index["copy.9"] == "unet/down0/attn0/self_attn/down0/qkv"
    assert set(mixed) == {"fusion.10"}


def test_an_instruction_with_metadata_and_no_scope_has_none():
    hlo = _TPU_HLO.replace("%copy.7 = f32[2,8]{1,0} copy(%fusion.9)",
                           "%neg.6 = f32[2,8]{1,0} negate(%gte.0), metadata="
                           '{op_name="jit(f)/while/body/neg"}\n'
                           "  %copy.7 = f32[2,8]{1,0} copy(%fusion.9, %neg.6)")
    hlo = hlo.replace("fusion(%gte.0, %copy-done.4)", "fusion(%neg.6, %copy-done.4)")
    index, _ = traceparse.scope_index(hlo)
    assert "neg.6" not in index and index["fusion.9"] == "unet/conv_in"


# -- (d) the launch registry ----------------------------------------------


@pytest.mark.parametrize("site", list(LAUNCH_SITES))
def test_scope_index_is_built_when_asked_and_once(tiny_pipe, monkeypatch, site):
    module, launch_site, a_scope = LAUNCH_SITES[site]
    ledger = compile_ledger()
    launch_site(tiny_pipe)
    known = launches.programs(module)
    assert known, f"{module} was launched and not kept"
    launch = known[-1]
    leaves = jax.tree_util.tree_leaves((launch.args, launch.kwargs))
    assert not any(isinstance(x, (jax.Array, np.ndarray)) for x in leaves)
    shapes = [x for x in leaves if isinstance(x, jax.ShapeDtypeStruct)]
    assert len(shapes) > 10                # shapes, never arrays
    if site == "sweep_dp4":                # ... with the mesh they were staged on
        assert {x.sharding.mesh.shape["dp"] for x in shapes if x.sharding} == {4}
    # a warm launch keeps nothing more and lowers nothing
    before, t0 = len(known), ledger.rows()[-1].ended_at
    launch_site(tiny_pipe)
    assert len(launches.programs(module)) == before
    assert not ledger.rows("lower", "backend", "cache_hit", since=t0)

    parses = []
    parse = traceparse.scope_index
    monkeypatch.setattr(traceparse, "scope_index",
                        lambda text: parses.append(1) or parse(text))
    if launch.index is None:               # nobody has asked yet
        index, mixed = launches.scope_index(module)
        assert parses == [1] and launch.built_from == "memory"
    index, mixed = launches.scope_index(module)
    assert len(parses) <= 1                # asking again parses nothing
    assert a_scope in set(index.values())
    assert launches.scope_index("jit__never_launched") is None


@pytest.mark.parametrize("site", ["text2image", "encode", "sweep", "sweep_phase2"])
def test_a_launch_keeps_how_its_self_sites_ran(tiny_pipe, site):
    """Counted while the program was traced: the controller's sites as
    ``edited``, the others by the implementation ``nn.fused_attention`` chose
    from their shape (no kernel off the TPU, and no tiny site has 1024 keys).
    None of these launches returns a store, so ``store=True`` (the
    ``text2image`` site's) holds no site: the count is the ``store=False``
    controller's."""
    from p2p_tpu.controllers.base import controller_touches

    module, launch_site, _ = LAUNCH_SITES[site]
    launch_site(tiny_pipe)
    launch = launches.programs(module)[-1]
    got = launch.self_site_counts
    if site == "encode":
        assert got == {} and launch.self_sites == {}
        return
    ctrl = _ctrl(tiny_pipe, store=False)
    if site == "sweep_phase2":
        ctrl = phase2_controller(ctrl)
    metas = [m for m in unet_layout(TINY.unet).metas if not m.is_cross]
    edited = sum(1 for m in metas if controller_touches(ctrl, m))
    want = {"edited": edited, "einsum": len(metas) - edited}
    assert got == {k: v for k, v in want.items() if v}
    # each site with its keys and head width; no kernel, so no geometry
    assert {(s.keys, s.head_dim, s.geometry) for s in launch.self_sites.values()} == {
        (m.pixels, m.channels // m.heads, None) for m in metas}


@pytest.mark.parametrize("return_store", [True, False],
                         ids=["store taken back", "no reader"])
def test_a_stored_self_site_is_the_controllers_only_for_a_reader(tiny_pipe, return_store):
    """``store=True`` above a 4² edit window: the three 8² self sites are
    counted ``edited`` (and the store has bytes) for the caller that takes the
    store back, and are ``fused_attention``'s when nobody reads it
    (``AttnLayout.for_readers``); the launch says which it was."""
    from p2p_tpu.controllers.base import controller_touches

    ctrl = factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, tiny_pipe.tokenizer, self_max_pixels=4 * 4,
        max_len=TINY.text.max_length, store=True)
    _, _, store = text2image(tiny_pipe, PROMPTS, ctrl, num_steps=STEPS,
                             return_store=return_store)
    launch = launches.programs("jit__text2image_jit")[-1]
    layout = unet_layout(TINY.unet).for_readers(ctrl, return_store)
    assert launch.args[3] == layout
    metas = [m for m in layout.metas if not m.is_cross]
    edited = sum(1 for m in metas if controller_touches(ctrl, m))
    assert edited == (4 if return_store else 1)
    assert launch.self_site_counts == {"edited": edited, "einsum": len(metas) - edited}
    assert launch.store_bytes == sum(s.size * 4 for s in store)
    assert (launch.store_bytes > 0) == return_store
    assert f"controller store {launch.store_bytes} bytes" in launch.describe_sites()


@pytest.mark.parametrize("site, line", [
    (launches.SelfSite(4096, 40, "kernel", (256, 4096, 2048), "bfloat16"),
     "4096x40 kernel 256x4096x2048 bf16"),
    (launches.SelfSite(9216, 64, "kernel", (512, 3072, 1536), "float32"),
     "9216x64 kernel 512x3072x1536 f32"),
    (launches.SelfSite(1024, 80, "kernel", (1024, 1024, 1024), "float16"),
     "1024x80 kernel 1024x1024x1024 float16"),
    # a record of before the field, and the sites off the kernel: no word
    (launches.SelfSite(4096, 40, "kernel", (256, 4096, 2048)),
     "4096x40 kernel 256x4096x2048"),
    (launches.SelfSite(256, 160, "edited"), "256x160 edited"),
    (launches.SelfSite(576, 64, "einsum"), "576x64 einsum"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_launch_line_names_a_kernel_sites_operand_width(site, line):
    assert str(site) == line
    launch = launches.Launch("jit_f", None, (), {}, self_sites={0: site, 1: site})
    assert f"; 2 of {line}; controller store 0 bytes" in launch.describe_sites()


@pytest.mark.parametrize("precision, word", [(None, "bf16"), ("highest", "f32")])
def test_a_traced_kernel_site_records_the_width_it_was_handed(
        tiny_pipe, monkeypatch, precision, word):
    """Traced as on a TPU at a 64² latent, whose three 64² and three 32² self
    sites have 4,096 and 1,024 keys: they take the flash kernel, and the
    record (and the launch line) says in what dtype the kernel was handed f32
    arrays: bfloat16 at the process's default matmul precision, their own
    where the process asked for more. The tile is the table's answer at that
    width."""
    import dataclasses

    from p2p_tpu.models import init_unet, nn
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.ops import schedulers as sched_mod

    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(
        TINY, name="tiny-64", unet=dataclasses.replace(TINY.unet, sample_size=64))
    ctrl = _ctrl(tiny_pipe, store=False)
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl, False)
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    sched = sched_mod.schedule_from_config(STEPS, cfg.scheduler, kind="ddim")
    ctx = jnp.zeros((2, cfg.unet.context_len, cfg.unet.context_dim))
    lat = jnp.zeros((2, 64, 64, cfg.unet.in_channels))
    launches.built()                        # the sites noted from here on
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        sampler._text2image_jit.trace(unet, vae, cfg, layout, sched, "ddim", ctx,
                                      ctx, lat, ctrl, jnp.float32(7.5), None, False)
        operand = nn.flash_operand_dtype(jnp.float32)
    sites = dict(launches._traced_sites)
    on_kernel = [s for s in sites.values() if s.how == "kernel"]
    shapes = [(4096, 16), (1024, 32)]       # keys, head width: three sites each
    tiles = {shape: nn.flash_block(*shape, operand.itemsize) for shape in shapes}
    assert sorted((s.keys, s.head_dim, s.geometry, s.operand) for s in on_kernel) == sorted(
        [shape + (tiles[shape], operand.name) for shape in shapes] * 3)
    assert all(s.operand == "" and s.geometry is None
               for s in sites.values() if s.how != "kernel")
    line = launches.Launch("jit_f", None, (), {}, self_sites=sites).describe_sites()
    for (keys, d_head), tile in tiles.items():
        assert (f"; 3 of {keys}x{d_head} kernel {'x'.join(map(str, tile))} {word};"
                in line)


def test_a_stale_cached_executable_is_compiled_once_more(monkeypatch):
    """The cache's key leaves metadata out: an executable cached before the
    scopes were named is served with none. The index then compiles past it."""
    texts = iter(['ENTRY %main (x: f32[2]) -> f32[2] {\n  ROOT %a.1 = f32[2] add(%x, %x), '
                  'metadata={op_name="jit(f)/add"}\n}\n',
                  'ENTRY %main (x: f32[2]) -> f32[2] {\n  ROOT %a.1 = f32[2] add(%x, %x), '
                  'metadata={op_name="jit(f)/unet/conv_in/add"}\n}\n'])
    options = []

    class Lowered:
        def compile(self, compiler_options=None):
            options.append(compiler_options)
            return self

        def as_text(self):
            return next(texts)

    launch = launches.Launch("jit_f", type("F", (), {"lower": lambda *a, **k: Lowered()})(),
                             (), {})
    launches._build(launch)
    assert options == [None, launches._PAST_THE_CACHE]
    assert launch.index == {"a.1": "unet/conv_in"}
