"""A stored map is kept only for a reader (ISSUE 30).

The set of store slots follows from who reads the store
(``AttnLayout.for_readers``): the caller that takes it back
(``text2image(return_store=True)``) reads every slot of the layout's
``StoreConfig``, LocalBlend reads its cross maps, nobody else reads anything.
A self site above the edit window whose map nobody reads is then untouched
and runs fused, whatever ``Controller.store`` says; the images do not move.

TINY's pyramid: self and cross sites at 16² (never stored: the bound is 8²),
8² (three of each, stored) and 4² (the mid block's, stored). With an edit
window of 4² the three 8² self sites are the store-only ones.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.analysis import contracts
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.base import controller_touches, init_store_state
from p2p_tpu.engine.sampler import (_text2image_jit, encode_prompts,
                                    phase2_controller, text2image)
from p2p_tpu.models import TINY, init_unet, nn
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models.config import unet_layout
from p2p_tpu.obs import launches
from p2p_tpu.parallel.sweep import seed_latents, sweep, sweep_phase1

PROMPTS = ["a cat riding a bike", "a dog riding a bike"]
STEPS = 4
GATE = 2
WINDOW = 4 * 4                      # self maps injected into: the 4² site
BLEND_SIDE = 8                      # TINY stores no 16² cross map
LAYOUT = unet_layout(TINY.unet)
STORE_ONLY = [m for m in LAYOUT.metas
              if not m.is_cross and m.store_slot is not None and m.pixels > WINDOW]
READERS = ["nobody", "blend", "caller"]


def _ctrl(pipe, blend=False, store=True):
    lb = (factory.local_blend(PROMPTS, ["cat", "dog"], pipe.tokenizer,
                              num_steps=STEPS, resolution=BLEND_SIDE,
                              th=(0.9, 0.9),      # random weights: maps are flat
                              max_len=TINY.text.max_length) if blend else None)
    return factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, pipe.tokenizer, local_blend=lb,
        self_max_pixels=WINDOW, max_len=TINY.text.max_length, store=store)


def _case(pipe, readers):
    """``(controller, return_store)`` of a reader case."""
    return _ctrl(pipe, blend=readers == "blend"), readers == "caller"


def _slots(layout):
    return [m.layer_idx for m in layout.stored_metas()]


def _shapes(state):
    return [tuple(s.shape) for s in state]


def _run(pipe, ctrl, return_store):
    images, _, store = text2image(pipe, PROMPTS, ctrl, num_steps=STEPS,
                                  rng=jax.random.PRNGKey(7),
                                  return_store=return_store)
    return np.asarray(images), store, launches.programs("jit__text2image_jit")[-1]


@pytest.fixture(scope="module")
def pipe(tiny_pipe):
    """The tiny pipeline under a name of its own (a static argument of the
    jitted programs): every program here is traced here, so the newest kept
    launch of a module is this file's."""
    return dataclasses.replace(
        tiny_pipe, config=dataclasses.replace(TINY, name="tiny-readers"))


@pytest.fixture(scope="module")
def runs(pipe):
    """One ``text2image`` per reader case, and the plain ``store=False`` edit."""
    out = {r: _run(pipe, *_case(pipe, r)) for r in READERS}
    out["no store"] = _run(pipe, _ctrl(pipe, store=False), False)
    out["blend and caller"] = _run(pipe, _ctrl(pipe, blend=True), True)
    return out


# -- the rule ------------------------------------------------------------------

def test_the_layout_has_store_only_self_sites():
    assert [m.resolution for m in STORE_ONLY] == [8, 8, 8]
    assert len(_slots(LAYOUT)) == 8


@pytest.mark.parametrize("readers", READERS)
def test_slots_follow_the_readers(tiny_pipe, readers):
    ctrl, return_store = _case(tiny_pipe, readers)
    got = LAYOUT.for_readers(LAYOUT.resolve(ctrl), return_store)
    want = {"nobody": [],
            "blend": [m.layer_idx for m in LAYOUT.blend_metas(BLEND_SIDE)],
            "caller": _slots(LAYOUT)}[readers]
    assert _slots(got) == want and want == sorted(want)
    # slots count from 0 in call order: the state tuple holds just them
    assert [m.store_slot for m in got.stored_metas()] == list(range(len(want)))
    if readers == "caller":
        assert got is LAYOUT                  # the whole StoreConfig, unchanged
    # nothing but the slots moves, and the rule is idempotent
    strip = lambda lay: [dataclasses.replace(m, store_slot=None) for m in lay.metas]  # noqa: E731
    assert strip(got) == strip(LAYOUT) and got.store_cfg == LAYOUT.store_cfg
    assert got.for_readers(ctrl, return_store) == got
    # an edited site is the controller's whatever the readers are
    for m in LAYOUT.metas:
        edited = m.is_cross or m.pixels <= WINDOW
        stored = m.layer_idx in want
        assert controller_touches(ctrl, got.metas[m.layer_idx]) == (edited or stored)


@pytest.mark.parametrize("ctrl", ["none", "no store", "identity"])
def test_a_controller_that_keeps_no_store_has_no_reader_but_the_caller(tiny_pipe, ctrl):
    ctrl = {"none": None, "no store": _ctrl(tiny_pipe, store=False),
            "identity": factory.empty_control()}[ctrl]
    assert _slots(LAYOUT.for_readers(ctrl)) == []
    assert LAYOUT.for_readers(ctrl, True) is LAYOUT


def test_blend_slots_are_the_blends_cross_maps_renumbered(tiny_pipe):
    got = LAYOUT.for_readers(_ctrl(tiny_pipe, blend=True))
    metas = got.blend_metas(BLEND_SIDE)
    assert [m.layer_idx for m in metas] == [3, 7, 9]
    assert [m.store_slot for m in metas] == [0, 1, 2]
    assert all(m.is_cross and m.resolution == BLEND_SIDE for m in metas)
    assert _shapes(init_store_state(got, 2)) == [(2, 2, 64, 16)] * 3


# -- (a) the caller reads: the parent's program ---------------------------------

def test_return_store_keeps_the_whole_store_and_the_parents_program(tiny_pipe, runs):
    images, store, launch = runs["caller"]
    # the layout the program was traced with is the whole one, as before this
    # rule existed: same function, same static arguments, same jaxpr
    assert launch.args[3] == LAYOUT and launch.args[12] is True
    direct = list(launch.args)
    direct[3] = unet_layout(TINY.unet)
    assert str(_text2image_jit.trace(*launch.args, **launch.kwargs).jaxpr) == str(
        _text2image_jit.trace(*direct, **launch.kwargs).jaxpr)
    assert _shapes(store) == _shapes(init_store_state(LAYOUT, 2))
    assert launch.store_bytes == sum(s.size * 4 for s in store) > 0
    # every slot accumulated STEPS maps whose rows sum to 1
    for m in LAYOUT.stored_metas():
        np.testing.assert_allclose(np.asarray(store[m.store_slot]).sum(-1), STEPS,
                                   rtol=1e-4)
    assert launch.self_site_counts == {"edited": 4, "einsum": 3}


def test_a_read_store_keeps_the_injected_site_on_the_parents_path(pipe, runs,
                                                                  monkeypatch):
    """Under ``return_store=True`` the window's self site has a slot, so its
    reader gets the post-edit map: the site stays edited on the materialized
    path, and images and store are those of the rule before ISSUE 37 (every
    edited site materialized), bit for bit."""
    from p2p_tpu.models import unet

    images, store, launch = runs["caller"]
    window = [m for m in LAYOUT.metas if not m.is_cross and m.pixels <= WINDOW]
    assert [m.resolution for m in window] == [4]
    for m in window:
        assert m.store_slot is not None
        noted = launch.self_sites[m.layer_idx]
        assert (noted.how, noted.geometry) == ("edited", None)
    monkeypatch.setattr(unet, "controller_only_injects", lambda c, m: False)
    parent = dataclasses.replace(
        pipe, config=dataclasses.replace(TINY, name="tiny-materialized"))
    p_images, p_store, p_launch = _run(parent, *_case(parent, "caller"))
    np.testing.assert_array_equal(images, p_images)
    assert len(store) == len(p_store) > 0
    for a, b in zip(store, p_store):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert p_launch.self_sites == launch.self_sites


# -- (b) nobody reads -------------------------------------------------------------

def test_store_without_a_reader_is_no_store(tiny_pipe, runs):
    images, store, launch = runs["nobody"]
    assert store == () and launch.store_bytes == 0
    assert _slots(launch.args[3]) == []
    assert "controller store 0 bytes" in launch.describe_sites()
    # the store-only self sites left the controller; the 4² site stays edited
    for m in STORE_ONLY:
        assert launch.self_sites[m.layer_idx].how == "einsum"
    assert launch.self_site_counts == {"edited": 1, "einsum": 6}
    # the images are the materialized path's, bit for bit
    np.testing.assert_array_equal(images, runs["caller"][0])
    np.testing.assert_array_equal(images, runs["no store"][0])
    # and the program is the ``store=False`` edit's
    plain = runs["no store"][2]
    assert launch.self_site_counts == plain.self_site_counts
    assert plain.args[3] == launch.args[3]


def _probs_shapes(pipe, ctrl, return_store, monkeypatch):
    """Shapes of the CFG-doubled softmaxes in the jaxpr of the program
    ``text2image`` builds, traced as on a TPU (the flash kernel where
    ``nn.flash_block`` has a geometry; nothing runs) at a 64² latent, whose
    32² self sites have 1,024 keys."""
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(
        TINY, name="tiny-64", unet=dataclasses.replace(TINY.unet, sample_size=64))
    layout = unet_layout(cfg.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl, return_store)
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: init_unet(key, cfg.unet))
    vae = jax.eval_shape(lambda: vae_mod.init_vae(key, cfg.vae))
    from p2p_tpu.ops import schedulers as sched_mod

    sched = sched_mod.schedule_from_config(STEPS, cfg.scheduler, kind="ddim")
    ctx = jnp.zeros((2, cfg.unet.context_len, cfg.unet.context_dim))
    lat = jnp.zeros((2, 64, 64, cfg.unet.in_channels))
    traced = _text2image_jit.trace(unet, vae, cfg, layout, sched, "ddim", ctx, ctx,
                                   lat, ctrl, jnp.float32(7.5), None, return_store)
    program = contracts.Program("text2image", traced.jaxpr, group_batch=2,
                                gate=None, metrics=False)
    return contracts._materialized_probs_eqns(program)


def test_no_probabilities_of_a_store_only_site_in_the_jaxpr(tiny_pipe, monkeypatch):
    """At a 64² latent the bound is 32² and a 16² window leaves the 32² self
    sites store-only: (4, 2, 1024, 1024) f32 each when materialized."""
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, tiny_pipe.tokenizer, self_max_pixels=16 * 16,
        max_len=TINY.text.max_length, store=True)
    site = (4, 2, 1024, 1024)
    kept = _probs_shapes(tiny_pipe, ctrl, True, monkeypatch)
    assert kept.count(site) == 3                     # the detector sees them
    free = _probs_shapes(tiny_pipe, ctrl, False, monkeypatch)
    assert site not in free
    # what is left is the edit's: the 16² self site and every cross site
    assert sorted(set(free)) == [(4, 2, 256, 16), (4, 2, 256, 256),
                                 (4, 2, 1024, 16), (4, 2, 4096, 16)]
    assert sorted(set(kept)) == sorted(set(free) | {site})


@pytest.mark.parametrize("return_store", [False, True], ids=["nobody", "caller"])
def test_an_injected_site_reaches_the_kernel_unless_its_map_is_read(
        tiny_pipe, monkeypatch, return_store):
    """A 32² window at a 64² latent: the three 32² self sites are injected
    into. With nobody reading the store they run the flash kernel on the base
    row's q and k (ISSUE 37): edited, with the kernel's tile, and no (4, 2,
    1024, 1024) map in the program. The caller who takes the store back keeps
    them materialized, as before."""
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, tiny_pipe.tokenizer, self_max_pixels=32 * 32,
        max_len=TINY.text.max_length, store=True)
    launches.built()
    shapes = _probs_shapes(tiny_pipe, ctrl, return_store, monkeypatch)
    window = [s for s in launches._traced_sites.values() if s.keys == 32 * 32]
    assert len(window) == 3 and {s.how for s in window} == {"edited"}
    site = (4, 2, 1024, 1024)
    if return_store:
        assert shapes.count(site) == 3
        assert {(s.geometry, s.operand) for s in window} == {(None, "")}
    else:
        assert site not in shapes
        assert {(s.geometry, s.operand) for s in window} == {
            ((1024, 1024, 1024), "bfloat16")}


# -- (c) LocalBlend reads -----------------------------------------------------------

def test_local_blend_keeps_its_maps_and_its_images(tiny_pipe, runs):
    images, store, launch = runs["blend"]
    assert store == ()                               # nothing is taken back
    traced_with = launch.args[3]
    assert _slots(traced_with) == [m.layer_idx for m in LAYOUT.blend_metas(BLEND_SIDE)]
    assert launch.store_bytes == 3 * 2 * 2 * 64 * 16 * 4
    for m in STORE_ONLY:
        assert launch.self_sites[m.layer_idx].how == "einsum"
    both_images, both_store, both = runs["blend and caller"]
    assert both.args[3] == LAYOUT
    assert _shapes(both_store) == _shapes(init_store_state(LAYOUT, 2))
    np.testing.assert_array_equal(images, both_images)
    # the blend did something: the edit without it lands elsewhere
    assert not np.array_equal(images, runs["nobody"][0])


# -- (d) sweep and the hand-off ask the same function ---------------------------------

def _sweep_inputs(pipe, ctrl):
    ctrls = jax.tree_util.tree_map(lambda x: x[None], ctrl)
    cond = encode_prompts(pipe, PROMPTS)
    uncond = encode_prompts(pipe, [""] * len(PROMPTS))
    ctx = jnp.concatenate([uncond, cond], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(7), 1, len(PROMPTS), pipe.latent_shape)
    return ctx, lats, ctrls


def _request(blend):
    from p2p_tpu.serve.request import Request

    return Request(request_id="r", prompt=PROMPTS[0], target=PROMPTS[1],
                   mode="replace", steps=STEPS, gate=GATE, seed=7,
                   blend_words="cat,dog" if blend else None,
                   blend_resolution=BLEND_SIDE if blend else None)


@pytest.mark.parametrize("readers", ["nobody", "blend"])
def test_sweep_and_handoff_build_the_programs_store(pipe, readers):
    """A sweep returns no store, so its readers are LocalBlend or nobody; the
    phase-1 pool's carry, the hand-off's template and the contracts' zero
    carry hold the same store leaves."""
    from p2p_tpu.serve.handoff import carry_template
    from p2p_tpu.serve.request import prepare

    ctrl, _ = _case(pipe, readers)
    want = init_store_state(LAYOUT.for_readers(ctrl), 2)
    assert len(want) == {"nobody": 0, "blend": 3}[readers]
    ctx, lats, ctrls = _sweep_inputs(pipe, ctrl)
    carry = sweep_phase1(pipe, ctx, lats, ctrls, num_steps=STEPS, gate=GATE)
    launch = launches.programs("jit__sweep_phase1_jit")[-1]
    assert _slots(launch.args[2]) == _slots(LAYOUT.for_readers(ctrl))
    assert launch.store_bytes == sum(s.size * 4 for s in want)
    assert _shapes(carry.state) == [(1,) + s for s in _shapes(want)]
    assert _shapes(contracts._zero_carry(pipe, ctrl).state) == _shapes(want)
    # phase 2's slice of the controller names the same readers
    two = phase2_controller(ctrl)
    if readers == "blend":
        assert LAYOUT.for_readers(two) == LAYOUT.for_readers(ctrl)
    else:
        assert two is None
    # the request's own template: the serve factory's controller (store=True)
    prep = prepare(_request(blend=readers == "blend"), pipe)
    assert prep.controller.store and (prep.controller.blend is not None) == (readers == "blend")
    assert _shapes(carry_template(pipe, prep)["carry"].state) == _shapes(want)
    # the ungated sweep runs the same sites
    images, _ = sweep(pipe, ctx, lats, ctrls, num_steps=STEPS)
    swept = launches.programs("jit__sweep_jit")[-1]
    assert _slots(swept.args[3]) == _slots(LAYOUT.for_readers(ctrl))
    assert swept.store_bytes == launch.store_bytes
    assert swept.self_site_counts == {"edited": 1, "einsum": 6}


def test_the_callers_store_is_the_layouts(tiny_pipe, runs):
    """The third reader case exists for ``text2image`` alone: what comes back
    is indexed by the layout the caller holds (``average_attention``)."""
    from p2p_tpu.controllers.base import average_attention

    _, store, _ = runs["caller"]
    avg = average_attention(LAYOUT, store, STEPS)
    assert {k: len(v) for k, v in avg.items()} == {
        "down_cross": 1, "mid_cross": 1, "up_cross": 2,
        "down_self": 1, "mid_self": 1, "up_self": 2}
