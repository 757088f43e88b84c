"""The entry layer's spans (ISSUE 27): on ``time.monotonic_ns()``, the same
record in the ring and in the profiler's trace by span id, one
``entry.text2image`` root a call with the work's parts below it, and
``entry.controller`` a root of its own."""

import time

import jax
import pytest

from p2p_tpu.controllers import factory
from p2p_tpu.engine.sampler import encode_prompts, text2image
from p2p_tpu.models import TINY
from p2p_tpu.obs import spans as spans_mod
from p2p_tpu.parallel.sweep import seed_latents, sweep

PROMPTS = ["a cat on a mat", "a dog on a mat"]
STEPS = 2


@pytest.fixture
def annotations(monkeypatch):
    """Every ``jax.profiler.TraceAnnotation`` a span opened, as (name,
    kwargs); the collector watch's ``gc.gen<g>`` ones are not spans'."""
    opened = []

    class Recorded:
        def __init__(self, name, **kwargs):
            if not name.startswith("gc.gen"):
                opened.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    spans_mod.clear()
    return opened


def _ctrl(pipe):
    return factory.attention_replace(
        PROMPTS, STEPS, 0.8, 0.4, pipe.tokenizer, self_max_pixels=16 * 16,
        max_len=TINY.text.max_length, store=False)


def _finished(events):
    """Finished spans by id; the collector's ``gc.collect`` events, which
    land under whatever span was open, are not the entry points'."""
    return {e["span"]: e for e in events
            if e["event"] == "span_end" and e["name"] != "gc.collect"}


def test_ring_event_and_annotation_are_one_record_by_id(annotations):
    before = time.monotonic_ns()
    with spans_mod.span("outer", lanes=4) as outer:
        with spans_mod.span("inner") as inner:
            pass
    after = time.monotonic_ns()
    assert annotations == [("outer", {"span": outer, "parent": 0}),
                           ("inner", {"span": inner, "parent": outer})]
    events = spans_mod.events()
    assert [(e["span"], e["parent"]) for e in events] == [
        (outer, None), (inner, outer), (inner, outer), (outer, None)]
    stamps = [e["t_ns"] for e in events]
    assert stamps == sorted(stamps) and before <= stamps[0] and stamps[-1] <= after
    assert all("ts_ms" not in e for e in events)
    end = events[-1]
    assert end["dur_ms"] == pytest.approx((stamps[-1] - stamps[0]) / 1e6)


def test_disabled_spans_leave_no_event_and_open_no_annotation(annotations):
    spans_mod.set_enabled(False)
    try:
        with spans_mod.span("ghost") as sid:
            assert sid is None

        @spans_mod.span("ghost.decorated")
        def work():
            return 7

        assert work() == 7
    finally:
        spans_mod.set_enabled(True)
    assert spans_mod.events() == [] and annotations == []


def test_text2image_is_one_root_with_the_parts_of_the_call(tiny_pipe, annotations):
    ctrl = _ctrl(tiny_pipe)
    text2image(tiny_pipe, PROMPTS, ctrl, num_steps=STEPS)
    done = _finished(spans_mod.events())
    by_name = {}
    for e in done.values():
        by_name.setdefault(e["name"], []).append(e)
    # the controller is built before the call: a root of its own
    (controller,) = by_name["entry.controller"]
    assert controller["parent"] is None
    assert (controller["kind"], controller["prompts"], controller["steps"]) == (
        "attention_replace", 2, STEPS)
    (root,) = by_name["entry.text2image"]
    assert root["parent"] is None
    children = [e for e in done.values() if e["parent"] == root["span"]]
    assert sorted(e["name"] for e in children) == [
        "entry.encode", "entry.encode", "entry.prepare", "entry.tokenize",
        "entry.tokenize", "sampler.text2image"]
    start = {e["span"]: e["t_ns"] for e in spans_mod.events()
             if e["event"] == "span_start"}
    for e in children:                        # nested inside the root, in time
        assert start[root["span"]] <= start[e["span"]] <= e["t_ns"] <= root["t_ns"]
    assert sum(e["dur_ms"] for e in children) <= root["dur_ms"]
    (prepare,) = by_name["entry.prepare"]
    assert (prepare["steps"], prepare["batch"]) == (STEPS, 2)
    assert [e["prompts"] for e in by_name["entry.tokenize"]] == [2, 2]
    assert all(e["tokens"] == 2 * TINY.unet.context_len for e in by_name["entry.encode"])
    (dispatch,) = by_name["sampler.text2image"]
    assert (dispatch["steps"], dispatch["batch"]) == (STEPS, 2)
    # and each is in the trace under the same ids
    assert {(n, k["span"], k["parent"]) for n, k in annotations} == {
        (e["name"], e["span"], e["parent"] or 0) for e in done.values()}


def test_sweep_is_one_root_with_prepare_and_dispatch(tiny_pipe, annotations):
    import jax.numpy as jnp

    ctrls = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (1,) + x.shape),
                                   _ctrl(tiny_pipe))
    cond = encode_prompts(tiny_pipe, PROMPTS)
    ctx = jnp.concatenate([encode_prompts(tiny_pipe, [""] * 2), cond], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(1), 1, 2, tiny_pipe.latent_shape)
    spans_mod.clear()
    sweep(tiny_pipe, ctx, lats, ctrls, num_steps=STEPS)
    done = _finished(spans_mod.events())
    (root,) = [e for e in done.values() if e["name"] == "entry.sweep"]
    children = [e["name"] for e in done.values() if e["parent"] == root["span"]]
    assert sorted(children) == ["entry.prepare", "entry.prepare", "sampler.sweep"]
