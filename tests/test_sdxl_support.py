"""What `sdxl` (SDXL-base-1.0 at 1024², PR 36) asks of the program, at the toy
preset `tiny_xl` on the CPU: transformer depth by level with no attention at
the top, two text towers into one context, the pooled text and the sizes
beside the time step, kernels stored in bfloat16, a decode sized by bytes.
``text2image`` against the plain reference
(``benchmarks/reference/latent_diffusion_xl.py``); ``sweep``, both serve
pools and inversion against ``text2image``; and the parts one by one."""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import check as check_mod
from benchmarks.lib import flops, harness, pipeline
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.base import StoreConfig, build_layout, controller_touches
from p2p_tpu.engine import inversion
from p2p_tpu.engine.sampler import (Pipeline, _encode_jit, _text2image_jit,
                                    encode_prompts, text2image)
from p2p_tpu.models import TINY, TINY_V, init_text_encoder, init_unet, nn
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models.conditioning import Conditioning, zeros_for
from p2p_tpu.models.config import (PRESET_CONFIGS, SDXL, TINY_XL, unet_attn_specs,
                                   unet_layout)
from p2p_tpu.models.unet import apply_unet, embed_added
from p2p_tpu.obs import launches
from p2p_tpu.ops import schedulers as sched_mod
from p2p_tpu.parallel.sweep import sweep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_XL_JSON = os.path.join(ROOT, "tests", "benchmark", "rehearsal_xl", "bench",
                            "configs", "tiny_xl.json")
SDXL_JSON = os.path.join(ROOT, "benchmarks", "configs", "sdxl.json")
PROMPTS = ("a red cat eating a burger in the forest",
           "a red dog eating a burger in the forest")
REFINED = ("a red cat eating a burger in the forest",
           "a red cat eating a tasty burger in the dusty forest")
KEY = (20261005, 36)
EDIT = {"num_steps": 4, "guidance_scale": 5.0, "cross_replace_steps": 0.8,
        "self_replace_steps": 0.4, "self_max_pixels": 36}


@pytest.fixture(scope="module")
def tiny_xl():
    """The toy preset with the benchmark's seeded weights (kernels in
    bfloat16, as ``lib/weights.py`` fills the types the initialisers give),
    and its reference."""
    with open(TINY_XL_JSON) as f:
        config = json.load(f)
    pipe, weights = pipeline.build(config, 2147483659)
    return config, pipe, weights, harness.load_module("reference", "latent_diffusion_xl")


def _controller(pipe, kind, prompts, **kw):
    return getattr(factory, "attention_" + kind)(
        list(prompts), EDIT["num_steps"], EDIT["cross_replace_steps"],
        EDIT["self_replace_steps"], pipe.tokenizer,
        self_max_pixels=EDIT["self_max_pixels"],
        max_len=pipe.tokenizer.model_max_length, store=True, **kw)


def _program(pipe, kind="replace", prompts=PROMPTS, **kw):
    images, _, _ = text2image(
        pipe, list(prompts), _controller(pipe, kind, prompts),
        num_steps=EDIT["num_steps"], guidance_scale=EDIT["guidance_scale"],
        scheduler="ddim", rng=jnp.asarray(KEY, jnp.uint32), **kw)
    return np.asarray(images)


def _reference(tiny_xl, kind="replace", prompts=PROMPTS):
    config, pipe, weights, ref = tiny_xl
    edit = dict(EDIT, kind=kind)
    x_T = ref.noise(KEY, (1,) + pipe.latent_shape)
    align = {k: jnp.asarray(v) for k, v in ref.alignment(config, edit, prompts).items()}
    img, _ = ref.make_edit_fn(config, edit)(
        weights, x_T, jnp.asarray(ref.prompt_ids(config, prompts)), align)
    return np.asarray(ref.to_uint8(img))


def _err(served, reference):
    return max(check_mod.image_rel_err(served[j], reference[j])
               for j in range(len(reference)))


#: Both sides compute in float32 on the CPU from the same bfloat16 kernels
#: (each widened where it is used), so they differ by the order of their sums
#: alone: a handful of the 27,648 uint8 values of an image land one level
#: apart (readings 9e-7 to 3e-6). 2e-4 is `tiny_v`'s limit, for its reasons,
#: and stands three orders under the least fault below.
LIMIT = 2e-4


# -- (a) the program against the plain reference ------------------------------

@pytest.mark.parametrize("kind,prompts", [("replace", PROMPTS), ("refine", REFINED)],
                         ids=("replace", "refine"))
def test_tiny_xl_matches_the_plain_reference(tiny_xl, kind, prompts):
    _, pipe, _, _ = tiny_xl
    assert pipe.config is TINY_XL and isinstance(pipe.text_params, list)
    assert [pipe.config.unet.resolution_at(i) for i in range(3)] == [24, 12, 6]
    assert _err(_program(pipe, kind, prompts), _reference(tiny_xl, kind, prompts)) < LIMIT


def test_the_comparison_is_tight_enough(tiny_xl, monkeypatch):
    """bfloat16 arrays, the pooled text of another prompt, and another size
    vector each fail the limit by orders."""
    _, pipe, _, _ = tiny_xl
    reference = _reference(tiny_xl)
    assert _err(_program(pipe, dtype=jnp.bfloat16), reference) > 100 * LIMIT
    other = dataclasses.replace(pipe.config.unet, addition_sizes=(96, 96, 8, 8, 96, 96))
    cropped = dataclasses.replace(pipe, config=dataclasses.replace(pipe.config, unet=other))
    assert _err(_program(cropped), reference) > 100 * LIMIT
    import p2p_tpu.engine.sampler as sampler_mod
    encode = sampler_mod.encode_prompts
    monkeypatch.setattr(
        sampler_mod, "encode_prompts",
        lambda p, prompts, dtype=jnp.float32: (lambda c: c._replace(pooled=c.pooled[::-1]))(
            encode(p, prompts, dtype)))
    assert _err(_program(pipe), reference) > 10 * LIMIT     # 48 of 96 inputs of one MLP


# -- (b) the other entry points against text2image ----------------------------

@pytest.mark.parametrize("dp", [None, 2], ids=["one device", "dp=2"])
def test_sweep_matches_text2image(tiny_xl, dp):
    """Two groups from two seeds under one vmapped program, on one device
    and with the group axis of every leaf of the conditioning sharded over
    two: each group is the sequential call's images (to a uint8 level: other
    batch widths sum in another order, the tolerance of tests/test_serve.py)."""
    from p2p_tpu.parallel import make_mesh

    _, pipe, _, _ = tiny_xl
    mesh = None if dp is None else make_mesh(dp)
    ctrl = _controller(pipe, "replace", PROMPTS)
    cond = encode_prompts(pipe, list(PROMPTS))
    uncond = encode_prompts(pipe, [""] * 2)
    assert isinstance(cond, Conditioning) and cond.added is None
    one = jax.tree.map(lambda u, c: jnp.concatenate([u, c]), uncond, cond)
    ctx = jax.tree.map(lambda a: jnp.stack([a, a]), one)
    keys = [jax.random.PRNGKey(s) for s in (11, 12)]
    lats = jnp.stack([jnp.broadcast_to(
        jax.random.normal(k, (1,) + pipe.latent_shape), (2,) + pipe.latent_shape)
        for k in keys])
    ctrls = jax.tree.map(lambda x: jnp.stack([x, x]), ctrl)
    images, _ = sweep(pipe, ctx, lats, ctrls, num_steps=EDIT["num_steps"],
                      guidance_scale=EDIT["guidance_scale"], mesh=mesh)
    for g, k in enumerate(keys):
        want, _, _ = text2image(pipe, list(PROMPTS), ctrl, rng=k,
                                num_steps=EDIT["num_steps"],
                                guidance_scale=EDIT["guidance_scale"])
        d = np.abs(np.asarray(images[g]).astype(np.int16) - np.asarray(want).astype(np.int16))
        assert d.max() <= 1


@pytest.mark.parametrize("gate", [None, 0.5], ids=["one pool", "two pools"])
def test_serving_matches_text2image(tiny_xl, gate):
    """Ungated requests ride the monolithic sweep runner, gated ones cross
    the hand-off between the phase-1 and phase-2 pools with the conditional
    half of their conditioning (context and pooled text) in the carry."""
    from p2p_tpu.cli import controller_from_opts
    from p2p_tpu.serve import Request, serve_forever

    _, pipe, _, _ = tiny_xl
    steps = 4
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    reqs = [Request(request_id=f"r{i}", prompt=prompts[0], target=prompts[1],
                    mode="replace", steps=steps, gate=gate, arrival_ms=0.0,
                    seed=200 + i) for i in range(2)]
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        recs = list(serve_forever(pipe, reqs, max_batch=2, max_wait_ms=5.0))
        ok = {r["request_id"]: r for r in recs if r["status"] == "ok"}
        assert len(ok) == 2
        ctrl = controller_from_opts(prompts, pipe.tokenizer, steps, mode="replace",
                                    cross_steps=0.8, self_steps=0.4)
        for i in range(2):
            want, _, _ = text2image(pipe, prompts, ctrl, num_steps=steps,
                                    guidance_scale=reqs[i].guidance,
                                    rng=jax.random.PRNGKey(200 + i), gate=gate)
            d = np.abs(ok[f"r{i}"]["images"].astype(np.int16)
                       - np.asarray(want).astype(np.int16))
            assert d.max() <= 1
    summary, = [r for r in recs if r["status"] == "summary"]
    assert (summary["phases"]["handoffs"] == 2) if gate else ("phases" not in summary
                                                             or not summary["phases"].get("handoffs"))


def test_handoff_template_is_the_presets_conditioning(tiny_xl, tmp_path):
    from p2p_tpu.engine.sampler import carry_spec
    from p2p_tpu.serve import Request
    from p2p_tpu.serve.handoff import carry_template, load_carry, spill_carry
    from p2p_tpu.serve.request import prepare

    _, pipe, _, _ = tiny_xl
    prep = prepare(Request(request_id="g", prompt=PROMPTS[0], target=PROMPTS[1],
                           mode="replace", steps=4, gate=0.5, seed=1), pipe)
    template = carry_template(pipe, prep)
    cond = encode_prompts(pipe, list(PROMPTS))
    assert jax.tree.structure(template["ctx"]) == jax.tree.structure(cond)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), template["ctx"]) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), cond)
    unit = dict(template, ctx=cond)
    path = str(tmp_path / "c.npz")
    assert spill_carry(unit, path) == carry_spec(template)
    back = load_carry(path, template)
    np.testing.assert_array_equal(back["ctx"].pooled, cond.pooled)
    assert isinstance(zeros_for(TINY, 2), jax.Array)


def test_null_text_leaves_the_pooled_vector_as_encoded(tiny_xl):
    """Replaying with per-step unconditional embeddings that are the empty
    prompt's own hidden states is the plain call, bit for bit: the pooled
    text beside them stays the empty prompt's. And inversion runs end to end,
    optimising hidden states of the context's width."""
    _, pipe, _, _ = tiny_xl
    ctrl = _controller(pipe, "replace", PROMPTS)
    kw = dict(num_steps=4, guidance_scale=5.0, rng=jnp.asarray(KEY, jnp.uint32))
    plain, x_t, _ = text2image(pipe, list(PROMPTS), ctrl, **kw)
    null = encode_prompts(pipe, [""])
    ups = jnp.broadcast_to(null.context[None], (4,) + null.context.shape)
    replay, _, _ = text2image(pipe, list(PROMPTS), ctrl, uncond_embeddings=ups, **kw)
    np.testing.assert_array_equal(np.asarray(replay), np.asarray(plain))

    art = inversion.invert(pipe, np.asarray(plain[0]), PROMPTS[0], num_steps=4,
                           num_inner_steps=2)
    assert art.uncond_embeddings.shape == (4, 1, 16, 80)
    assert np.all(np.isfinite(art.uncond_embeddings)) and np.all(np.isfinite(art.x_t))
    again, _, _ = text2image(pipe, [PROMPTS[0]], None, num_steps=4, guidance_scale=5.0,
                             latent=jnp.asarray(art.x_t),
                             uncond_embeddings=jnp.asarray(art.uncond_embeddings))
    assert again.shape == (1, 96, 96, 3)


def test_the_cli_edits_with_the_preset(tmp_path):
    from p2p_tpu.cli import main

    assert main(["edit", "--quiet", "--preset", "tiny_xl", "--source",
                 "a cat riding a bike", "--target", "a dog riding a bike",
                 "--steps", "2", "--out-dir", str(tmp_path)]) == 0
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".jpg")]) >= 2


# -- (c) the towers -------------------------------------------------------------

def _tower_by_hand(w, cfg, ids):
    """A CLIP text tower in numpy-style jnp: every layer's output."""
    f = lambda a: jnp.asarray(a, jnp.float32)
    x = f(w["token_embed"])[ids] + f(w["pos_embed"])[:ids.shape[1]]
    n, heads = ids.shape[1], cfg.num_heads
    mask = jnp.where(jnp.arange(n)[None] > jnp.arange(n)[:, None], -jnp.inf, 0.0)

    def ln(p, v):
        m = v.mean(-1, keepdims=True)
        return (v - m) / jnp.sqrt(((v - m) ** 2).mean(-1, keepdims=True) + 1e-5) \
            * p["scale"] + p["bias"]

    def lin(p, v):
        return v @ f(p["kernel"]) + (p["bias"] if "bias" in p else 0.0)

    act = (lambda v: v * jax.nn.sigmoid(1.702 * v)) if cfg.activation == "quick_gelu" \
        else (lambda v: jax.nn.gelu(v, approximate=False))
    outs = []
    for layer in w["layers"]:
        h = ln(layer["ln1"], x)
        q, k, v = (lin(layer[name], h).reshape(-1, n, heads, x.shape[-1] // heads)
                   .transpose(0, 2, 1, 3) for name in "qkv")
        p = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) * q.shape[-1] ** -0.5 + mask, -1)
        x = x + lin(layer["out"], (p @ v).transpose(0, 2, 1, 3).reshape(x.shape))
        x = x + lin(layer["fc2"], act(lin(layer["fc1"], ln(layer["ln2"], x))))
        outs.append(x)
    return outs, ln


def test_penultimate_states_and_pooled_text_against_a_forward_by_hand(tiny_xl):
    _, pipe, weights, _ = tiny_xl
    prompts = ["a red cat", "one small dog washing a teapot in the harbor"]
    got = encode_prompts(pipe, prompts)
    ids = np.asarray(pipe.tokenizer(prompts, max_length=16)["input_ids"], np.int32)
    eos = (ids == pipe.tokenizer.eos_token_id).argmax(1)
    assert list(eos) == [4, 10]
    hidden, pooled = [], None
    for w, cfg in zip(weights["text"], pipe.config.text):
        outs, ln = _tower_by_hand(w, cfg, ids)
        hidden.append(outs[-2])                   # no final LayerNorm on it
        if cfg.projection_dim is not None:
            at = outs[-1][np.arange(2), eos]
            pooled = ln(w["final_ln"], at) @ jnp.asarray(w["projection"]["kernel"], jnp.float32)
    np.testing.assert_allclose(got.context, jnp.concatenate(hidden, -1), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got.pooled, pooled, rtol=2e-5, atol=2e-5)
    assert got.context.shape == (2, 16, 32 + 48) and got.pooled.shape == (2, 48)
    # the first tower has no projection, and its last layer is never run:
    # with that layer's weights spoiled the conditioning is the same
    spoiled = [jax.tree.map(lambda a: a, weights["text"][0]), weights["text"][1]]
    spoiled[0]["layers"] = spoiled[0]["layers"][:-1] + [
        jax.tree.map(lambda a: a * jnp.nan, spoiled[0]["layers"][-1])]
    same = encode_prompts(dataclasses.replace(pipe, text_params=spoiled), prompts)
    np.testing.assert_array_equal(same.context, got.context)


def test_one_tower_presets_encode_to_the_array_they_always_did(tiny_pipe):
    enc = encode_prompts(tiny_pipe, ["a cat"])
    assert isinstance(enc, jax.Array) and enc.shape == (1, 16, 32)
    with pytest.raises(ValueError, match="takes a Conditioning"):
        apply_unet(None, TINY_XL.unet, jnp.zeros((1, 24, 24, 4)), 0, enc)
    with pytest.raises(ValueError, match="the hidden states"):
        apply_unet(None, TINY.unet, jnp.zeros((1, 16, 16, 4)), 0,
                   Conditioning(enc, enc[:, 0]))
    two = (TINY.text, TINY.text)
    with pytest.raises(ValueError, match="0 of 2 towers"):
        _encode_jit(init_text_encoder(jax.random.PRNGKey(0), two), two,
                    jnp.zeros((1, 16), jnp.int32), jnp.float32,
                    eos=jnp.zeros((1,), jnp.int32))


def test_loading_a_checkpoint_of_several_towers_says_what_it_waits_for(tmp_path):
    from p2p_tpu.models.checkpoint import load_pipeline
    from p2p_tpu.models.checkpoint_check import check_checkpoint

    for call in (lambda: load_pipeline(str(tmp_path), SDXL),
                 lambda: check_checkpoint(str(tmp_path), "sdxl")):
        with pytest.raises(NotImplementedError, match="several text towers"):
            call()


# -- (d) depth by level -----------------------------------------------------------

@pytest.mark.parametrize("preset,path", [("sdxl", SDXL_JSON), ("tiny_xl", TINY_XL_JSON)])
def test_site_order_is_the_counts(preset, path):
    """``unet_attn_specs`` in call order against ``lib/flops.py:unet_sites``
    from the configuration file: places, pixels and channels, a self site
    and then its cross site, and the names the scopes are built from."""
    with open(path) as f:
        uc = json.load(f)["unet"]
    cfg = PRESET_CONFIGS[preset].unet
    specs = unet_attn_specs(cfg)
    sites = flops.unet_sites(uc)
    assert len(specs) == 2 * len(sites)
    assert [(s[0], s[2] ** 2, s[5]) for s in specs[0::2]] == [
        (place, pixels, ch) for place, _, pixels, ch in sites]
    assert [s[1] for s in specs] == [False, True] * len(sites)
    metas = unet_layout(cfg).metas
    assert [f"{m.place}{m.layer_idx}" for m in metas if not m.is_cross] == \
        flops.self_site_names(uc)
    if preset == "sdxl":
        assert len(sites) == 70
        assert flops.self_site_names(uc)[0] == "down0" and f"{metas[-2].place}{metas[-2].layer_idx}" == "up138"
        assert sorted({(m.pixels, m.heads, m.channels // m.heads) for m in metas
                       if not m.is_cross}) == [(1024, 20, 64), (4096, 10, 64)]


def test_the_tree_holds_a_group_of_depth_d_as_one_norm_and_d_blocks():
    tree = jax.eval_shape(lambda: init_unet(jax.random.PRNGKey(0), SDXL.unet))
    assert [len(b["attns"]) for b in tree["down"]] == [0, 2, 2]
    assert [len(b["attns"]) for b in tree["up"]] == [3, 3, 0]
    assert [len(a["blocks"]) for a in tree["down"][1]["attns"]] == [2, 2]
    assert [len(a["blocks"]) for a in tree["up"][0]["attns"]] == [10, 10, 10]
    assert len(tree["mid"]["attn"]["blocks"]) == 10
    assert tree["add_fc1"]["kernel"].shape == (2816, 1280)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert 2.56e9 < count < 2.58e9                    # about 2.567 B parameters
    # an int for every level is what it was
    assert "add_fc1" not in jax.eval_shape(lambda: init_unet(jax.random.PRNGKey(0), TINY.unet))


def test_sdxl_cell_sites_by_the_layout(monkeypatch):
    """What `sdxl.edit-replace`'s controller (window 32², ``store=True``, no
    store taken back) runs, from the layout alone: the sixty sites at 1,024
    keys are the controller's, the ten at 4,096 are left to the flash
    kernel, which has a geometry for both shapes at heads of 64."""
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    whole = unet_layout(SDXL.unet)
    assert whole.edit_resolution() == 32 and whole.latent_size == 128
    ctrl = factory.attention_replace(list(PROMPTS), 50, 0.8, 0.4, HashWordTokenizer(),
                                     max_len=77, store=True)
    ctrl = whole.resolve(ctrl)
    assert ctrl.edit.self_max_pixels == 1024
    layout = whole.for_readers(ctrl)
    assert layout.latent_size == 128 and layout.num_store_slots == 0
    selfs = [m for m in layout.metas if not m.is_cross]
    touched = [m.pixels for m in selfs if controller_touches(ctrl, m)]
    assert len(selfs) == 70 and touched == [1024] * 60
    assert nn.flash_block(4096, 64, 2) == (256, 4096, 2048)
    assert nn.flash_block(1024, 64, 2) is not None


@pytest.mark.parametrize("sides,latent,want", [
    ((64, 32), 128, 32), ((96, 48, 24, 12), 96, 24), ((64, 32, 16, 8), 64, 16),
    ((96, 48, 24, 12), None, 24), ((12, 6), 24, 6)])
def test_edit_resolution(sides, latent, want):
    specs = [("down", False, s, 2, s * s) for s in sides]
    assert build_layout(specs, StoreConfig(), latent_size=latent).edit_resolution() == want


def test_edit_resolution_without_a_level_to_stand_on_raises():
    specs = [("down", False, s, 2, s * s) for s in (64, 32)]
    with pytest.raises(ValueError, match="no default edit resolution"):
        build_layout(specs, StoreConfig()).edit_resolution()      # 64 // 4 = 16
    with pytest.raises(ValueError, match="a quarter of the latent's side"):
        build_layout(specs, StoreConfig(), latent_size=512).edit_resolution()


# -- (e) the added embedding ------------------------------------------------------

def test_added_embedding_is_computed_once_ahead_of_the_scan(tiny_xl):
    """``embed_added`` fills the conditioning's third leaf from the pooled
    text and the preset's sizes; ``apply_unet`` computes the same where it
    is None; and in the sampling program the two products sit outside the
    loop, under ``unet/add_embed``."""
    _, pipe, _, _ = tiny_xl
    cfg = pipe.config.unet
    cond = encode_prompts(pipe, list(PROMPTS))
    ahead = embed_added(pipe.unet_params, cfg, cond)
    assert ahead.added.shape == (2, cfg.time_embed_dim)
    assert embed_added(pipe.unet_params, cfg, ahead) is ahead
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 24, 4))
    a, _ = apply_unet(pipe.unet_params, cfg, x, 500, cond)
    b, _ = apply_unet(pipe.unet_params, cfg, x, 500, ahead)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    e = nn.timestep_embedding(jnp.asarray(cfg.addition_sizes, jnp.float32), 8).reshape(-1)
    vec = jnp.concatenate([cond.pooled, jnp.broadcast_to(e, (2, 48))], -1)
    assert vec.shape[-1] == cfg.addition_embed_in
    by_hand = nn.linear(pipe.unet_params["add_fc2"],
                        nn.silu(nn.linear(pipe.unet_params["add_fc1"], vec)))
    np.testing.assert_allclose(ahead.added, by_hand, rtol=1e-6, atol=1e-6)

    launch = _fresh_launch(pipe, steps=3)
    jaxpr = launch.fn.trace(*launch.args, **launch.kwargs).jaxpr
    where = {in_scan for in_scan, scope in _scopes_of(jaxpr.jaxpr)
             if "unet/add_embed" in scope}
    assert where == {False}
    assert any(in_scan and "unet/time_embed" in scope
               for in_scan, scope in _scopes_of(jaxpr.jaxpr))


def _fresh_launch(pipe, steps):
    """The launch of a sampling program no other test of this module has
    built (a step count of its own), so that it is the registry's newest."""
    before = len(launches.programs("jit__text2image_jit"))
    text2image(pipe, list(PROMPTS), _controller(pipe, "replace", PROMPTS),
               num_steps=steps, rng=jnp.asarray(KEY, jnp.uint32))
    known = launches.programs("jit__text2image_jit")
    assert len(known) == before + 1
    return known[-1]


def _scopes_of(jaxpr, in_scan=False):
    """``(inside a scan?, name stack)`` of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        yield in_scan, str(eqn.source_info.name_stack)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scopes_of(sub, in_scan or eqn.primitive.name == "scan")


# -- (f) storage and the decode ---------------------------------------------------

def test_kernels_are_stored_in_bfloat16_and_nothing_else_is():
    for tree in (init_unet(jax.random.PRNGKey(0), TINY_XL.unet),
                 init_text_encoder(jax.random.PRNGKey(1), TINY_XL.text),
                 vae_mod.init_vae(jax.random.PRNGKey(2), TINY_XL.vae)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            name = jax.tree_util.keystr(path)
            assert leaf.dtype == (jnp.bfloat16 if name.endswith("['kernel']")
                                  else jnp.float32), name
    for preset in ("tiny", "sd14", "sd21", "tiny_v", "ldm256"):
        pc = PRESET_CONFIGS[preset]
        shapes = pipeline.weight_shapes(pc)
        assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype(jnp.float32)}


def test_storage_width_is_not_arithmetic(tiny_xl):
    """``text2image`` with bfloat16-stored kernels equals ``text2image`` with
    the same values widened to float32 leaves under
    ``jax_default_matmul_precision="bfloat16"``, to the bit: a kernel is
    widened where it is used, so the stored width changes what is read and
    nothing that is computed. (On the CPU the precision setting does not
    round the other operand; on the chip the default precision already does.)"""
    _, pipe, _, _ = tiny_xl
    narrow = _program(pipe)
    wide = dataclasses.replace(pipe, **{
        part: jax.tree.map(lambda a: a.astype(jnp.float32), getattr(pipe, part))
        for part in ("unet_params", "text_params", "vae_params")})
    with jax.default_matmul_precision("bfloat16"):
        widened = _program(wide)
    np.testing.assert_array_equal(narrow, widened)


def test_chunked_decode_is_the_whole_one(tiny_xl, monkeypatch):
    _, pipe, _, _ = tiny_xl
    cfg = pipe.config.vae
    lat = jax.random.normal(jax.random.PRNGKey(5), (4, 24, 24, 4)) * 0.13
    assert vae_mod.decode_chunks(cfg, lat.shape) == 1
    whole = vae_mod.decode(pipe.vae_params, cfg, lat)
    for images, chunks in ((1, 4), (2, 2), (3, 2)):      # equal chunks only
        monkeypatch.setattr(vae_mod, "DECODE_CHUNK_BYTES", images * 96 * 96 * 16 * 4)
        assert vae_mod.decode_chunks(cfg, lat.shape) == chunks
        np.testing.assert_allclose(vae_mod.decode(pipe.vae_params, cfg, lat), whole,
                                   rtol=1e-5, atol=1e-5)
    text = jax.jit(lambda p, x: vae_mod.decode(p, cfg, x)).lower(
        pipe.vae_params, lat).as_text()
    assert text.count("stablehlo.convolution") > 4 * 10 and "stablehlo.while" not in text
    monkeypatch.undo()
    # by bytes: 2 x 768^2 x 128 x 4 B is one chunk, 2 x 1024^2 is two, and a
    # batch of three is never split unevenly
    sd = PRESET_CONFIGS["sd21"].vae
    assert vae_mod.decode_chunks(sd, (2, 96, 96, 4)) == 1
    assert vae_mod.decode_chunks(sd, (2, 64, 64, 4)) == 1
    assert vae_mod.decode_chunks(SDXL.vae, (2, 128, 128, 4)) == 2
    assert vae_mod.decode_chunks(SDXL.vae, (3, 128, 128, 4)) == 3
    assert vae_mod.decode_chunks(SDXL.vae, (4, 128, 128, 4)) == 4


def test_a_launch_keeps_weights_depth_and_chunks(tiny_xl, monkeypatch):
    _, pipe, weights, _ = tiny_xl
    monkeypatch.setattr(vae_mod, "DECODE_CHUNK_BYTES", 96 * 96 * 16 * 4)   # an image
    launch = _fresh_launch(pipe, steps=5)
    assert launch.unet_depth == (0, 1, 2) and launch.decode_chunks == 2

    def by_dtype(tree):
        out = {}
        for a in jax.tree.leaves(tree):
            out[str(a.dtype)] = out.get(str(a.dtype), 0) + a.size * a.dtype.itemsize
        return out

    assert launch.weights_bytes == {"unet": by_dtype(weights["unet"]),
                                    "vae": by_dtype(weights["vae"])}
    assert set(launch.weights_bytes["unet"]) == {"bfloat16", "float32"}
    encode = [p for p in launches.programs("jit__encode_jit")
              if "bfloat16" in p.weights_bytes["text"]][0]
    assert encode.weights_bytes == {"text": by_dtype(weights["text"])}
    assert encode.unet_depth == () and encode.decode_chunks == 0
    line = launch.describe_model()
    total = sum(n for by in launch.weights_bytes.values() for n in by.values())
    assert "transformer depth by level (0, 1, 2)" in line
    assert "decode_chunks 2" in line and f"weights_bytes {total} (unet bf16:" in line


# -- (g) the programs of the presets that were there ------------------------------

#: sha256 of ``_text2image_jit``'s and ``_encode_jit``'s lowered text for the
#: replace controller of two three-word prompts (4 steps, ungated), as the
#: parent's tree lowered them on this CPU backend (PR 35's tree; the script is
#: in PERF.md §6, PR 36). A preset with one tower and no added embedding
#: passes through the conditioning's tree maps as the array it is.
LOWERED_BY_THE_PARENT = {
    "tiny": ("70b20451c7b02dec", "0de8a7cf2f3ba3d1"),
    "tiny_v": ("765f90df40b8bff6", "0104cdb9f0fcc4f7"),
}


@pytest.mark.parametrize("preset", sorted(LOWERED_BY_THE_PARENT))
def test_lowered_programs_of_one_tower_presets_are_the_parents(preset):
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    cfg = PRESET_CONFIGS[preset]
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    text = jax.eval_shape(lambda: init_text_encoder(key, cfg.text))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    n = cfg.unet.context_len
    tok = HashWordTokenizer(model_max_length=n)
    ctrl = factory.attention_replace(["a cat sat", "a dog sat"], 4, 0.8, 0.4, tok, max_len=n)
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl, False)
    ts = sched_mod.schedule_from_config(4, cfg.scheduler, kind="ddim")
    ctx = jax.ShapeDtypeStruct((2, n, cfg.unet.context_dim), jnp.float32)
    lat = jax.ShapeDtypeStruct((2, cfg.latent_size, cfg.latent_size, 4), jnp.float32)
    lowered = _text2image_jit.lower(unet, vae, cfg, layout, ts, "ddim", ctx, ctx, lat, ctrl,
                                    jax.ShapeDtypeStruct((), jnp.float32), None, False, gate=4)
    encode = _encode_jit.lower(text, cfg.text, jax.ShapeDtypeStruct((2, n), jnp.int32),
                               jnp.float32)
    said = tuple(hashlib.sha256(t.as_text().encode()).hexdigest()[:16]
                 for t in (lowered, encode))
    assert said == LOWERED_BY_THE_PARENT[preset]
