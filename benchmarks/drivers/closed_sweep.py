"""Closed loop of batched edits: each request is one ``parallel.sweep`` of
``groups`` two-prompt edits, from the prompts as strings (prompt encoding
included) to the images on the host."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import check as check_mod
from benchmarks.lib.window import closed_loop, controller, new_state, warm_up


def _noise_shape(run, pipe):
    return (run.traffic["groups"], 1) + pipe.latent_shape


def _call(run, state, i: int) -> dict:
    import jax
    import jax.numpy as jnp

    from p2p_tpu.engine.sampler import encode_prompts
    from p2p_tpu.parallel.sweep import sweep

    req = state.requests(i)
    edit = run.traffic["edit"]
    pairs = req["prompts"]
    pipe = state.pipe
    with run.spans("controller", i):
        ctrls = [controller(pipe, edit, req["kind"], p) for p in pairs]
        ctrl = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls)
    with run.spans("encode", i):
        enc = encode_prompts(pipe, [""] + [p for pair in pairs for p in pair])
        context = jnp.stack([jnp.stack([enc[0], enc[0], enc[1 + 2 * g], enc[2 + 2 * g]])
                             for g in range(len(pairs))])
    with run.spans("noise", i):
        shape = _noise_shape(run, pipe)
        base = jax.random.normal(jnp.asarray(req["key"], jnp.uint32), shape,
                                 jnp.float32)
        latents = jnp.broadcast_to(base, (shape[0], 2) + shape[2:])
    with run.spans("sweep", i):
        images, final = sweep(pipe, context, latents, ctrl,
                              num_steps=edit["num_steps"],
                              guidance_scale=edit["guidance_scale"],
                              scheduler=edit["scheduler"], gate=edit.get("gate"))
    with run.spans("land", i):
        images, final = np.asarray(images), np.asarray(final)
    state.outputs[i] = (images, final)
    return {"index": i, "images": images.shape[0] * images.shape[1]}


def prepare(run):
    state = new_state(run)
    warm_up(run, state, _call)
    return state


def window(run, state) -> None:
    closed_loop(run, lambda i: _call(run, state, i))


def work(run, records) -> dict:
    edit = run.traffic["edit"]
    g = run.traffic["groups"]
    n = len(records)
    return {"unet_rows_full": n * g * 4 * edit["num_steps"], "unet_rows_cached": 0,
            "prompts": n * (1 + 2 * g), "images": n * g * 2,
            "steps": n * edit["num_steps"],
            "self_attn_rows": n * g * 4 * edit["num_steps"]}


def check(run, state) -> dict:
    import random

    import jax

    shape = _noise_shape(run, state.pipe)
    state.pipe = None
    jax.clear_caches()
    done = run.done
    picks = check_mod.sample_indices(len(done), run.traffic["check"]["requests"],
                                     run.seed)
    rng = random.Random(run.seed ^ 0xC0FFEE)
    items = []
    for p in picks:
        req = state.requests(done[p]["index"])
        images, final = state.outputs[req["index"]]
        for g in sorted(rng.sample(range(shape[0]),
                                   run.traffic["check"]["groups_per_request"])):
            items.append({"kind": req["kind"], "prompts": req["prompts"][g],
                          "key": req["key"], "noise_shape": shape, "noise_pick": g,
                          "images": images[g], "latents": final[g]})
    return check_mod.check_groups(run, state.weights, items)
