"""Closed loop of single edits: each request is one
``engine.sampler.text2image`` of a two-prompt edit, from the prompts as
strings to the images on the host."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import check as check_mod
from benchmarks.lib.window import (closed_loop, controller, new_state,
                                   trace_one_more, warm_up)


def _call(run, state, i: int) -> dict:
    import jax.numpy as jnp

    from p2p_tpu.engine.sampler import text2image

    req = state.requests(i)
    edit = run.traffic["edit"]
    prompts = req["prompts"][0]
    with run.spans("controller", i):
        ctrl = controller(state.pipe, edit, req["kind"], prompts)
    with run.spans("text2image", i):
        images, _, _ = text2image(
            state.pipe, list(prompts), ctrl, num_steps=edit["num_steps"],
            guidance_scale=edit["guidance_scale"], scheduler=edit["scheduler"],
            rng=jnp.asarray(req["key"], jnp.uint32), gate=edit.get("gate"))
    with run.spans("land", i):
        images = np.asarray(images)
    state.outputs[i] = images
    return {"index": i, "images": len(images)}


def prepare(run):
    state = new_state(run)
    warm_up(run, state, _call)
    return state


def window(run, state) -> None:
    closed_loop(run, lambda i: _call(run, state, i))


def trace_again(run, state) -> None:
    trace_one_more(run, lambda i: _call(run, state, i))


def work(run, records) -> dict:
    """The work of ``records`` for the operation count: U-Net forwards by
    rows of their batch, prompts encoded, images decoded, scan steps."""
    edit = run.traffic["edit"]
    n = len(records)
    return {"unet_rows_full": n * 4 * edit["num_steps"], "unet_rows_cached": 0,
            "prompts": n * 4, "images": n * 2, "steps": n * edit["num_steps"],
            "self_attn_rows": n * 4 * edit["num_steps"]}


def check(run, state) -> dict:
    import jax

    shape = (1,) + state.pipe.latent_shape
    state.pipe = None
    jax.clear_caches()
    done = run.done
    picks = check_mod.sample_indices(len(done), run.traffic["check"]["requests"],
                                     run.seed)
    items = []
    for p in picks:
        req = state.requests(done[p]["index"])
        items.append({"kind": req["kind"], "prompts": req["prompts"][0],
                      "key": req["key"], "noise_shape": shape, "noise_pick": slice(None),
                      "images": state.outputs[req["index"]]})
    return check_mod.check_groups(run, state.weights, items)
