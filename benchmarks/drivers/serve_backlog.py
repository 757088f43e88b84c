"""The service under a backlog: ``serve.serve_forever`` with every option at
its default but ``max_batch`` and ``max_wait_ms``, fed a stream that arrives
faster than it is served until ``--seconds`` of wall time have passed, then
drained. Requests alternate between the mix's kinds (gated replace, ungated
refine), each with its own prompts and seed.

Arrivals are evenly spaced on the engine's trace clock at the mix's
``arrival_rate_per_s``, some twice what the engine serves, so the queue only
grows, yet stays under the engine's ``queue_cap`` of 64 for the window's
length: nothing is rejected. (50 requests a second, as ``bench.py``'s short
trace has them, would have the engine reject some thousand requests in 30 s.)

The window starts when the engine pulls its second request from the stream:
the first pull is made as the engine is built, before its ``prewarm`` (one
request of each kind) has built and warmed the programs, which is set-up.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

from benchmarks.lib import check as check_mod
from benchmarks.lib.records import dispatches
from benchmarks.lib.window import new_state


def _request(run, state, i: int):
    from p2p_tpu.serve import Request

    req = state.requests(i)
    edit = run.traffic["edit"]
    source, target = req["prompts"][0]
    return Request(
        request_id=f"r{i}", prompt=source, target=target, mode=req["kind"],
        cross_steps=edit["cross_replace_steps"], self_steps=edit["self_replace_steps"],
        seed=req["key"][1] % 2 ** 31, steps=edit["num_steps"],
        scheduler=edit["scheduler"], guidance=edit["guidance_scale"],
        gate=edit["gate_of_kind"][req["kind"]],
        arrival_ms=max(0, i) * 1000.0 / run.traffic["arrival_rate_per_s"])


def prepare(run):
    state = new_state(run)
    state.issued = {}
    return state


def window(run, state) -> None:
    from p2p_tpu.serve import serve_forever

    kinds = run.traffic["edit"]["kinds"]
    n_traced = run.traffic.get("trace_calls", 2) if run.trace else 0
    live = SimpleNamespace(t0=None, first_call=None, tracing=False)

    def stream():
        i = 0
        while True:
            now = time.monotonic()
            if i == 1:                      # the engine is warm: the window opens
                live.t0 = now
                if n_traced:
                    run.start_trace()
                    live.tracing = True
                live.first_call = run.spans("call", 0)
                live.first_call.__enter__()
            elif i > 1 and now - live.t0 >= run.seconds:
                return
            state.issued[f"r{i}"] = now if i else None
            yield _request(run, state, i)
            i += 1

    prewarm = [_request(run, state, -1 - k) for k in range(len(kinds))]
    served = iter(serve_forever(state.pipe, stream(), prewarm=prewarm,
                                **run.traffic["engine"]))
    n = 0
    while True:
        if live.first_call is not None and n == 0:
            rec = next(served)
            live.first_call.__exit__(None, None, None)
        else:
            with run.spans("call", n):
                rec = next(served)
        if rec["status"] == "summary":
            state.summary = rec
            break
        now = time.monotonic()
        rid = rec["request_id"]
        out = {"index": int(rid[1:]), "t_start": state.issued[rid] or live.t0,
               "t_end": now, "engine": {k: v for k, v in rec.items() if k != "images"}}
        if rec["status"] == "ok":
            out["images"] = len(rec["images"])
            state.outputs[out["index"]] = rec["images"]
        else:
            out["failed"] = rec["status"] + ": " + str(rec.get("reason", ""))[:200]
        run.records.append(out)
        n += 1
        if live.tracing and n >= n_traced:
            run.stop_trace()
            live.tracing = False
    if live.tracing:
        run.stop_trace()
    run.t_setup_done = live.t0
    run.window = (live.t0, max(r["t_end"] for r in run.records))


def work(run, records) -> dict:
    """Useful work only: a padded lane's forwards are not counted."""
    edit = run.traffic["edit"]
    n = edit["num_steps"]
    full = cached = 0
    for r in records:
        gate = edit["gate_of_kind"][edit["kinds"][r["index"] % len(edit["kinds"])]]
        g = n if gate is None else int(round(gate * n))
        full += 4 * g
        cached += 2 * (n - g)
    steps = 0
    for kind, _, _, _, gate_step in dispatches(records).values():
        steps += {"mono": n, "phase1": gate_step, "phase2": n - gate_step}[kind]
    return {"unet_rows_full": full, "unet_rows_cached": cached,
            "prompts": 4 * len(records), "images": 2 * len(records), "steps": steps,
            "self_attn_rows": full + cached}


def check(run, state) -> dict:
    import random

    import jax

    shape = (1,) + state.pipe.latent_shape
    state.pipe = None
    jax.clear_caches()
    done = run.done
    kinds = run.traffic["edit"]["kinds"]
    rng = random.Random(run.seed ^ 0x5EED)
    picks = []
    for k in range(len(kinds)):                 # one request of every kind
        of_kind = [r for r in done if r["index"] % len(kinds) == k]
        if of_kind:
            picks.append(rng.choice(of_kind))
    items = []
    for rec in picks:
        req = state.requests(rec["index"])
        gate = run.traffic["edit"]["gate_of_kind"][req["kind"]]
        items.append({"kind": req["kind"], "prompts": req["prompts"][0],
                      "key": (0, req["key"][1] % 2 ** 31), "noise_shape": shape,
                      "noise_pick": slice(None), "images": state.outputs[rec["index"]],
                      "edit": {"gate": gate}})
    return check_mod.check_groups(run, state.weights, items)
