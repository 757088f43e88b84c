"""One sampling step, one executor (ISSUE 31): what the compiled programs
rest on now that ``gate=g`` is the uniform reuse table and every run goes
through ``_phase1_scan`` / ``_phase2_scan``.

The static facts the older gate path knew by construction are derived from
the segment's plan; each test fails if one of them is lost:

(a) the ungated program's scan carries the latents, the store's leaves and
    the solver's leaves: no guidance residual, no cache;
(b) the gated program's phase-2 scan carries no cache leaf (no site of the
    segment stores, so the body closes over the cache) and no array with
    the CFG-doubled batch;
(c) ``gate=g`` and the uniform table trace to one jaxpr, as do the ungated
    call and the table with ``cfg_gate == steps``;
(d) a non-uniform table whose flips all lie in phase 1 hands phase 2 a
    carry of the uniform table's shapes.
"""

import warnings

import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.analysis import jaxpr_walk
from p2p_tpu.controllers import factory
from p2p_tpu.engine import reuse as R
from p2p_tpu.engine.sampler import (
    _denoise_scan,
    _phase1_scan,
    carry_spec,
    resolve_reuse,
)
from p2p_tpu.models import TINY
from p2p_tpu.models.config import unet_layout
from p2p_tpu.ops import schedulers as sched_mod

STEPS = 8
GATE = 4
PROMPTS = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
SCHEDULERS = ["ddim", "plms", "dpm"]
B = len(PROMPTS)


def _setup(pipe, scheduler):
    """Layout (with LocalBlend's store slots, so the store has leaves),
    controller, solver tables and abstract inputs of one edit group."""
    lb = factory.local_blend(PROMPTS, [["burger"], ["lasagna"]],
                             pipe.tokenizer, num_steps=STEPS, resolution=8,
                             max_len=TINY.text.max_length)
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=0.4, self_replace_steps=0.25,
        tokenizer=pipe.tokenizer, self_max_pixels=8 * 8, local_blend=lb,
        max_len=TINY.text.max_length)
    layout = unet_layout(TINY.unet)
    ctrl = layout.resolve(ctrl)
    layout = layout.for_readers(ctrl)
    tsched = sched_mod.schedule_from_config(STEPS, TINY.scheduler,
                                            kind=scheduler)
    ctx = jnp.zeros((2 * B, TINY.unet.context_len, TINY.unet.context_dim))
    lats = jnp.zeros((B,) + pipe.latent_shape)
    return layout, ctrl, tsched, ctx, lats


def _trace(pipe, scheduler, layout, ctrl, tsched, ctx, lats, **kw):
    def run(c, l, g):
        return _denoise_scan(pipe.unet_params, TINY, layout, tsched,
                             scheduler, c, l, ctrl, g, **kw)

    return jax.make_jaxpr(run)(ctx, lats, jnp.float32(7.5))


def _carry_shapes(scan):
    """Shapes of the values the scan's body takes from one step to the
    next (its carry, after the constants)."""
    body = scan.params["jaxpr"].jaxpr
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    return sorted(tuple(v.aval.shape) for v in body.invars[nc:nc + nk])


def _leaf_shapes(tree):
    return sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_ungated_scan_carries_latents_store_and_solver_only(tiny_pipe,
                                                            scheduler):
    from p2p_tpu.controllers.base import init_store_state

    layout, ctrl, tsched, ctx, lats = _setup(tiny_pipe, scheduler)
    jaxpr = _trace(tiny_pipe, scheduler, layout, ctrl, tsched, ctx, lats)
    scans = jaxpr_walk.top_level_scans(jaxpr)
    assert len(scans) == 1, "an ungated run is one scan"
    state = init_store_state(layout, B, dtype=jnp.float32)
    assert state, "vacuous: the controller must keep a store"
    ms = sched_mod.init_multistep_state(scheduler, lats.shape, lats.dtype)
    want = _leaf_shapes((lats, state, ms))
    assert _carry_shapes(scans[0]) == want, (
        "the ungated scan carries something besides the latents, the "
        "store and the solver's state (a guidance residual? a cache?)")


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_gated_phase2_scan_carries_no_cache_and_no_doubled_batch(tiny_pipe,
                                                                 scheduler):
    layout, ctrl, tsched, ctx, lats = _setup(tiny_pipe, scheduler)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        gate = resolve_reuse(GATE, None, layout, tsched.timesteps.shape[0],
                             ctrl)[0]
    jaxpr = _trace(tiny_pipe, scheduler, layout, ctrl, tsched, ctx, lats,
                   gate=gate)
    scans = jaxpr_walk.top_level_scans(jaxpr)
    assert len(scans) == 2, "a gated run is a phase-1 and a phase-2 scan"
    ms = sched_mod.init_multistep_state(scheduler, lats.shape, lats.dtype)
    # phase 1 stores: its carry has the cache's (B, P, C) leaves
    cache = R.init_schedule_cache(
        layout, R.ReuseSchedule.uniform(gate, tsched.timesteps.shape[0],
                                        layout), B, phase=1, dtype=lats.dtype)
    assert cache and set(_leaf_shapes(cache)) <= set(_carry_shapes(scans[0]))
    # phase 2 only uses: latents and the solver's state, nothing else
    assert _carry_shapes(scans[1]) == _leaf_shapes((lats, ms))
    def doubled(scan):
        return jaxpr_walk.doubled_batch_shapes(
            jaxpr_walk.eqn_shapes(jaxpr_walk.scan_body(scan)), B)

    assert doubled(scans[0]), "vacuous: phase 1 runs the CFG-doubled batch"
    assert not doubled(scans[1]), (
        f"phase 2 holds CFG-doubled arrays: {doubled(scans[1])[:5]}")


@pytest.mark.parametrize("gate", [GATE, None], ids=["gated", "ungated"])
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_gate_and_its_uniform_table_trace_to_one_program(tiny_pipe,
                                                         scheduler, gate):
    layout, ctrl, tsched, ctx, lats = _setup(tiny_pipe, scheduler)
    num_scan = tsched.timesteps.shape[0]
    g = num_scan if gate is None else gate
    args = (tiny_pipe, scheduler, layout, ctrl, tsched, ctx, lats)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        by_gate = str(_trace(*args, gate=gate))
        table = R.resolve_schedule({"cfg_gate": g}, layout, num_scan, ctrl)
        assert table == R.ReuseSchedule.uniform(g, num_scan, layout)
        assert table.uniform_gate == g
        by_table = str(_trace(*args, reuse=table))
        # what a user's spec is keyed as: its gate, no table
        gate_step, reuse = resolve_reuse(None, {"cfg_gate": g}, layout,
                                         num_scan, ctrl)
        assert (gate_step, reuse) == (g, None)
        by_spec = str(_trace(*args, gate=gate_step, reuse=reuse))
    assert by_gate == by_table == by_spec


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_phase1_flips_hand_phase2_the_uniform_carry(tiny_pipe, scheduler):
    layout, ctrl, tsched, ctx, lats = _setup(tiny_pipe, scheduler)
    num_scan = tsched.timesteps.shape[0]
    uniform = R.ReuseSchedule.uniform(GATE, num_scan, layout)
    early = R.resolve_schedule(
        {"cfg_gate": GATE, "cross": {"*": GATE, "cross_attn/mid5": 2,
                                     "cross_attn/down1": 3}},
        layout, num_scan, ctrl)
    assert early.uniform_gate is None
    assert len(R.segments(layout, early, phase=1)) == 3

    def carry(table):
        return jax.eval_shape(
            lambda c, l, g: _phase1_scan(
                tiny_pipe.unet_params, TINY, layout, tsched, scheduler, c,
                l, ctrl, g, reuse=table),
            ctx, lats, jnp.float32(7.5))

    assert carry_spec(carry(early)) == carry_spec(carry(uniform))
