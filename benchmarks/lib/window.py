"""The closed loop: one client, the next request issued when the last one
has landed on the host."""

from __future__ import annotations

import time
import traceback
from types import SimpleNamespace

import jax

from . import pipeline, traffic


def new_state(run) -> SimpleNamespace:
    """What every driver keeps between set-up, window and check: the
    pipeline with the benchmark's weights, the request generator, and the
    outputs of the window by request index."""
    with run.spans("weights"):
        pipe, weights = pipeline.build(run.config, run.seed)
        jax.block_until_ready(weights)
    return SimpleNamespace(pipe=pipe, weights=weights, outputs={},
                           requests=traffic.Requests(run.traffic, run.seed))


def warm_up(run, state, call) -> None:
    """One request of every kind of the mix, so that each shape the window
    uses has run once; what they produce is dropped."""
    with run.spans("warm_up"):
        for k in range(len(run.traffic["edit"]["kinds"])):
            call(run, state, -1 - k)
    state.outputs.clear()


def controller(pipe, edit: dict, kind: str, prompts):
    """The edit's controller as the paper's code and ``bench.py`` build it:
    ``controllers.factory.attention_<kind>``, found by the kind's name.
    ``max_len`` is the tokenizer's ``model_max_length``: the controller
    aligns the ids that tokenizer makes, and ``pipe.config.text`` is one
    tower's configuration in some presets and a sequence of them in others
    (``lib/pipeline.py`` builds the tokenizer from the first tower)."""
    from p2p_tpu.controllers import factory

    return getattr(factory, "attention_" + kind)(list(prompts), edit["num_steps"], edit["cross_replace_steps"],
                edit["self_replace_steps"], pipe.tokenizer,
                self_max_pixels=edit["self_max_pixels"],
                max_len=pipe.tokenizer.model_max_length, store=edit["store"])


def closed_loop(run, call) -> None:
    """``call(i) -> record`` runs request ``i`` to its images on the host.
    Requests are issued until ``run.seconds`` have passed; the last one is
    finished inside the window. With ``run.trace`` the profiler records the
    first ``trace_calls`` requests of the window."""
    n_traced = run.traffic.get("trace_calls", 2) if run.trace else 0
    tracing = False
    t0 = time.monotonic()
    i = 0
    while True:
        if n_traced and i == 0:
            run.start_trace()
            tracing = True
        t_start = time.monotonic()
        try:
            with run.spans("call", i):
                rec = call(i)
        except Exception as e:                        # a failed request is counted
            traceback.print_exc()
            rec = {"index": i, "failed": f"{type(e).__name__}: {e}"[:300]}
        rec.update(t_start=t_start, t_end=time.monotonic())
        run.records.append(rec)
        i += 1
        if tracing and i == n_traced:
            run.stop_trace()
            tracing = False
        if time.monotonic() - t0 >= run.seconds:
            break
    if tracing:
        run.stop_trace()
    run.window = (t0, time.monotonic())


def trace_one_more(run, call) -> None:
    """One more request after the window, traced on its own, in place of
    traced calls whose trace is not whole. Its record goes to
    ``run.retakes``: the window's requests, rate and check stay as they
    were."""
    i = len(run.records) + len(run.retakes)
    run.start_trace()
    t_start = time.monotonic()
    try:
        with run.spans("call", i):
            rec = call(i)
    except Exception as e:
        traceback.print_exc()
        rec = {"index": i, "failed": f"{type(e).__name__}: {e}"[:300]}
    rec.update(t_start=t_start, t_end=time.monotonic())
    run.stop_trace()
    run.retakes.append(rec)
