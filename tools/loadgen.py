"""Deterministic synthetic arrival-trace generator for the serve layer.

Emits the serve JSONL request format (``p2p_tpu.serve.request.Request``)
with virtual ``arrival_ms`` stamps drawn from a seeded RNG — the same seed
always produces byte-identical traces, so the bench ``serve`` rehearsal and
the tests replay exactly the load they claim to.

Two arrival processes:

- ``poisson`` — exponential interarrivals at ``--rate`` requests/second:
  the steady-traffic model the dynamic batcher's occupancy is measured on.
- ``burst``  — groups of ``--burst-size`` simultaneous arrivals separated
  by ``--burst-gap-ms`` of silence: the backpressure/queue-depth stressor.

Requests cycle through a small prompt corpus of 2-prompt replace edits
sharing one compile key (seeds and prompts vary — traced values — so the
whole trace rides one compiled program per bucket; that is the point of
compile-key bucketing). ``--distinct-keys N`` spreads the trace over N
step-counts instead, for cache-pressure experiments. ``--gate-mix`` draws
each request's phase-gate spec from a weighted distribution (e.g.
``0.5:2,off:1``) with the same seeded RNG, so a trace actually exercises
the serve layer's phase hand-off and mixed-phase packing; the default
(no mix, no ``--gate``) keeps every request ungated — byte-identical to
pre-gate-mix traces. ``--tenant-mix``/``--tier-mix`` (ISSUE 12) draw the
SLO scheduling fields (``tenant``, ``tier``) per request the same way —
each mix on its OWN derived RNG stream, so adding or dropping any mix
leaves arrivals, seeds and the other mixes byte-identical. ``--zipf S``
(ISSUE 13) draws each request's *identity* (prompt pair + seed — its
semantic-cache content) from a Zipf(S) rank distribution over
``--zipf-universe`` identities on the same separate-stream discipline, so
popular requests repeat the way real traffic does while arrivals and
deadlines stay byte-identical to the non-zipf trace. ``--diurnal``
(ISSUE 19) modulates the poisson arrival *rate* through a sinusoidal
day-curve — a deterministic multiplier on each drawn gap, so the base
RNG stream is consumed identically and switching the mode off restores
the byte-identical flat trace; the curve's phase offset rides its own
derived stream. Elastic-serving drills use it for realistic pressure
swings (peaks that justify a scale-up, troughs that justify a shrink).

    python tools/loadgen.py --n 48 --mode poisson --rate 20 --seed 0 \
        --steps 4 --out demo.jsonl

``--duration-ms`` switches to the streaming long-trace mode
(:func:`generate_stream`): requests are emitted one line at a time until
the virtual-clock horizon, never materialized — tools/soak.py drives
hours-equivalent traces through it. The RNG draws per request (gap, seed,
optional gate) in request order, so the first K requests of a stream are
byte-identical to the finite ``--n K`` trace with the same seed — the
seed-stable prefix contract pinned in tests/test_loadgen.py.

Compat note (ISSUE 9): the per-request draw order replaced the original
vectorized draws (all gaps first, then seeds), so a given (seed, n)
poisson trace has different arrivals/seeds than the same invocation
produced before the lifecycle PR. Every in-repo consumer compares
within-run (drills, parity legs, bench A/B), but committed BENCH rounds
recorded before the change ran a *different seeded workload* for their
``serve``/``resilience`` blocks than post-change rounds will.

Two optional schedule sections make a trace a chaos drill
(tools/chaos_drill.py):

- ``--cancel-rate`` interleaves seeded ``{"cancel": <id>}`` markers into
  the stream — each victim is cancelled one arrival after it was admitted,
  so cancellation-before-dispatch is actually exercised.
- ``--fault-rate`` emits a ``serve.chaos.FaultPlan`` JSON next to the
  trace (``--fault-plan-out``, default ``<out>.faults.json``): each
  request id draws a fault kind with the given probability from the same
  seed, so trace + plan regenerate byte-identically together.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

_CORPUS = (
    ("a squirrel eating a burger", "a squirrel eating a lasagna"),
    ("a cat riding a bike", "a dog riding a bike"),
    ("a painting of a lighthouse", "a painting of a windmill"),
    ("a bowl of apples on a table", "a bowl of oranges on a table"),
)


def _parse_mix(spec: str, what: str, convert) -> List[tuple]:
    """Shared ``value:weight,...`` mix parser: ``off``/``none`` meaning
    the field is absent, a bare entry meaning weight 1, weights positive.
    ``convert`` maps the raw value string to its typed form."""
    out: List[tuple] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            val, w_str = part.rsplit(":", 1)
            weight = float(w_str)
        else:
            val, weight = part, 1.0
        if weight <= 0:
            raise ValueError(f"{what} weight must be positive in {part!r}")
        val = val.strip()
        out.append((None if val in ("off", "none") else convert(val),
                    weight))
    if not out:
        raise ValueError(f"empty {what} {spec!r}")
    return out


def parse_gate_mix(spec: str) -> List[tuple]:
    """``"0.5:2,off:1,auto:1"`` → ``[(0.5, 2.0), (None, 1.0), ('auto',
    1.0)]`` — weighted gate specs, ``off``/``none`` meaning ungated, a
    bare entry meaning weight 1. Weights must be positive."""
    def convert(val):
        if val == "auto":
            return "auto"
        return float(val) if "." in val else int(val)

    return _parse_mix(spec, "gate mix", convert)


def parse_name_mix(spec: str, what: str = "mix") -> List[tuple]:
    """``"premium:1,best_effort:3"`` / ``"acme:2,globex:1,off:1"`` →
    weighted *string* values for the ``--tier-mix``/``--tenant-mix``
    per-request draws (``off``/``none`` = the request carries no such
    field). Same syntax and weight rules as :func:`parse_gate_mix`."""
    return _parse_mix(spec, what, str)


def parse_diurnal(spec: str) -> dict:
    """Parse the ``--diurnal`` value: ``on`` (defaults) or a comma
    ``k=v`` list over ``period_ms`` (one full day-curve cycle of virtual
    time), ``low`` and ``high`` (the rate multiplier at trough/peak).
    The defaults swing a 4 s virtual day between 0.25× and 4× the base
    rate — wide enough that an elastic mesh crosses both its scale-up
    and scale-down thresholds every cycle."""
    out = {"period_ms": 4000.0, "low": 0.25, "high": 4.0}
    s = (spec or "").strip()
    if s not in ("", "on", "default"):
        for part in s.split(","):
            if "=" not in part:
                raise ValueError(f"--diurnal expects 'on' or 'k=v,...', "
                                 f"got {spec!r}")
            k, v = part.split("=", 1)
            k = k.strip()
            if k not in out:
                raise ValueError(f"unknown --diurnal field {k!r}; valid: "
                                 f"{', '.join(sorted(out))}")
            out[k] = float(v)
    if out["period_ms"] <= 0:
        raise ValueError(f"--diurnal period_ms must be positive, "
                         f"got {out['period_ms']}")
    if not 0 < out["low"] <= out["high"]:
        raise ValueError(f"--diurnal needs 0 < low <= high, got "
                         f"low={out['low']} high={out['high']}")
    return out


def generate_stream(
    duration_ms: Optional[float] = None,
    *,
    n: Optional[int] = None,
    mode: str = "poisson",
    rate_per_s: float = 20.0,
    seed: int = 0,
    steps: int = 50,
    scheduler: str = "ddim",
    burst_size: int = 8,
    burst_gap_ms: float = 500.0,
    deadline_ms: Optional[float] = None,
    distinct_keys: int = 1,
    gate=None,
    gate_mix: Optional[List[tuple]] = None,
    tenant_mix: Optional[List[tuple]] = None,
    tier_mix: Optional[List[tuple]] = None,
    zipf_s: Optional[float] = None,
    zipf_universe: int = 32,
    diurnal: Optional[dict] = None,
):
    """Yield request dicts in arrival order until ``arrival_ms`` would
    exceed ``duration_ms`` (and/or ``n`` requests have been produced; both
    ``None`` = unbounded) — the streaming long-trace mode: a multi-hour
    virtual-clock soak trace is never materialized in memory.

    **Seed-stable prefix contract** (pinned in tests/test_loadgen.py): the
    RNG draws per request, in request order — one interarrival gap, one
    seed, then (with a mix) one gate/tenant/tier draw, each on its own
    separate derived stream — so any prefix of a stream is independent of
    the horizon: the first K requests are byte-identical for every
    ``duration_ms``/``n`` ≥ K, and :func:`generate_trace` is literally
    ``list(generate_stream(n=K))``. Every mix rides its *own* derived RNG
    stream, so adding (or dropping) one mix never perturbs arrivals,
    seeds, or another mix's draws — a tenant/tier-mixed trace is
    byte-identical to the mix-less trace everywhere but its own fields
    (the ``--gate-mix`` discipline).

    ``zipf_s`` (ISSUE 13) switches popularity on: each request's
    *identity* — its (prompt pair, seed), i.e. its semantic-cache content
    — is drawn from a Zipf(s) rank distribution over ``zipf_universe``
    distinct identities, so popular requests repeat the way real traffic
    does and the serve layer's content-addressed cache has something to
    hit. The rank draws (and the fixed identity table) ride their OWN
    derived RNG streams and the main stream's per-request seed draw still
    happens (discarded), so arrivals, deadlines and every other mix stay
    byte-identical to the non-zipf trace — the ``--gate-mix``
    discipline.

    ``diurnal`` (ISSUE 19, :func:`parse_diurnal` dict) modulates the
    poisson *rate* through a sinusoidal day-curve: each drawn gap is
    divided by a deterministic multiplier evaluated at the current
    virtual time, so the base stream's draw order and count are
    untouched — ``diurnal=None`` reproduces the flat trace byte-for-byte
    (pinned in tests/test_loadgen.py). The curve's phase offset is one
    draw on its own derived stream (the separate-stream discipline), so
    different seeds peak at different times of "day"."""
    import math

    import numpy as np

    if mode not in ("poisson", "burst"):
        raise ValueError(f"mode must be 'poisson' or 'burst', got {mode!r}")
    if rate_per_s <= 0:
        raise ValueError(f"rate_per_s must be positive, got {rate_per_s}")
    if duration_ms is not None and duration_ms < 0:
        raise ValueError(f"duration_ms must be >= 0, got {duration_ms}")
    if zipf_s is not None and zipf_s <= 0:
        raise ValueError(f"zipf s must be positive, got {zipf_s}")
    if zipf_universe < 1:
        raise ValueError(f"zipf universe must be >= 1, got {zipf_universe}")
    day_mult = None
    if diurnal is not None:
        if mode != "poisson":
            raise ValueError("diurnal modulates the poisson rate; "
                             "mode 'burst' has no rate to modulate")
        d_period = float(diurnal.get("period_ms", 4000.0))
        d_low = float(diurnal.get("low", 0.25))
        d_high = float(diurnal.get("high", 4.0))
        if d_period <= 0 or not 0 < d_low <= d_high:
            raise ValueError(f"bad diurnal spec {diurnal!r}: needs "
                             f"period_ms > 0 and 0 < low <= high")
        # One draw on the curve's own derived stream (the --gate-mix
        # discipline): the phase offset, so different seeds peak at
        # different times of "day". Everything else is a pure function
        # of virtual time — no per-request draws, so the base stream is
        # consumed identically with the mode on or off.
        d_phase = float(np.random.RandomState(seed ^ 0xD1A7A1)
                        .random_sample()) * d_period

        def day_mult(t_ms):
            x = 0.5 * (1.0 - math.cos(
                2.0 * math.pi * (t_ms + d_phase) / d_period))
            return d_low + (d_high - d_low) * x

    def _mix_drawer(mix, salt):
        # A separate derived stream per mix (the with_cancels idiom):
        # draws must not perturb the arrival/seed stream or each other.
        total_w = sum(w for _, w in mix)
        cuts = np.cumsum([w / total_w for _, w in mix])
        mix_rng = np.random.RandomState(seed ^ salt)

        def draw():
            x = mix_rng.random_sample()
            return mix[int(np.searchsorted(cuts, x, side="right"))
                       if x < cuts[-1] else len(mix) - 1][0]
        return draw

    draw_gate = (_mix_drawer(gate_mix, 0x6A7E)
                 if gate_mix is not None else None)
    draw_tenant = (_mix_drawer(tenant_mix, 0x7E2A47)
                   if tenant_mix is not None else None)
    draw_tier = (_mix_drawer(tier_mix, 0x3C11E7)
                 if tier_mix is not None else None)
    draw_rank = None
    if zipf_s is not None:
        # Identity table: a FIXED zipf_universe of draws up front on its
        # own derived stream (independent of n/duration — the prefix-
        # stability invariant), then one rank draw per request on a
        # second derived stream. p(rank r) ∝ (r+1)^-s.
        id_rng = np.random.RandomState(seed ^ 0x21BF52)
        id_seeds = [int(id_rng.randint(0, 2 ** 31 - 1))
                    for _ in range(zipf_universe)]
        w = np.array([(r + 1.0) ** (-zipf_s) for r in range(zipf_universe)])
        zcuts = np.cumsum(w / w.sum())
        zipf_rng = np.random.RandomState(seed ^ 0x21BF53)

        def draw_rank():
            x = zipf_rng.random_sample()
            return (int(np.searchsorted(zcuts, x, side="right"))
                    if x < zcuts[-1] else zipf_universe - 1)
    rng = np.random.RandomState(seed)
    at = 0.0
    i = 0
    while True:
        if n is not None and i >= n:
            return
        if mode == "poisson":
            # The gap is drawn for every request (i=0's is discarded, not
            # skipped) so per-request RNG consumption is uniform — the
            # prefix-stability invariant.
            gap = float(rng.exponential(1000.0 / rate_per_s))
            if day_mult is not None:
                # Dividing the gap by the rate multiplier at the current
                # virtual time IS the rate modulation (thinning-free, so
                # the base draw count never changes).
                gap /= day_mult(at)
            if i:
                at += gap
        else:
            at = (i // burst_size) * burst_gap_ms
        if duration_ms is not None and at > duration_ms:
            return
        src, tgt = _CORPUS[i % len(_CORPUS)]
        # The per-request seed draw ALWAYS happens (uniform RNG
        # consumption — arrivals stay byte-identical under --zipf, whose
        # rank draw then overrides the request's identity).
        seed_draw = int(rng.randint(0, 2 ** 31 - 1))
        if draw_rank is not None:
            rank = draw_rank()
            src, tgt = _CORPUS[rank % len(_CORPUS)]
            seed_draw = id_seeds[rank]
        req = {
            "request_id": f"{mode}-{seed:04d}-{i:04d}",
            "prompt": src,
            "target": tgt,
            "mode": "replace",
            "steps": steps + (i % distinct_keys if distinct_keys > 1 else 0),
            "scheduler": scheduler,
            "seed": seed_draw,
            "arrival_ms": round(float(at), 3),
        }
        req_gate = draw_gate() if draw_gate is not None else gate
        if req_gate is not None:
            req["gate"] = req_gate
        if draw_tenant is not None:
            tenant = draw_tenant()
            if tenant is not None:
                req["tenant"] = tenant
        if draw_tier is not None:
            tier = draw_tier()
            if tier is not None:
                req["tier"] = tier
        if deadline_ms is not None:
            req["deadline_ms"] = deadline_ms
        yield req
        i += 1


def generate_trace(
    n: int,
    mode: str = "poisson",
    rate_per_s: float = 20.0,
    seed: int = 0,
    steps: int = 50,
    scheduler: str = "ddim",
    burst_size: int = 8,
    burst_gap_ms: float = 500.0,
    deadline_ms: Optional[float] = None,
    distinct_keys: int = 1,
    gate=None,
    gate_mix: Optional[List[tuple]] = None,
    tenant_mix: Optional[List[tuple]] = None,
    tier_mix: Optional[List[tuple]] = None,
    zipf_s: Optional[float] = None,
    zipf_universe: int = 32,
    diurnal: Optional[dict] = None,
) -> List[dict]:
    """Build ``n`` request dicts sorted by ``arrival_ms`` (deterministic in
    ``seed``) — the finite materialized form of :func:`generate_stream`,
    and byte-identical to its first ``n`` yields (the seed-stable prefix
    contract). ``gate_mix`` (:func:`parse_gate_mix` pairs) draws each
    request's gate from the weighted distribution — it overrides ``gate``,
    and the draws ride a separate seed-derived RNG stream, so arrivals and
    seeds stay byte-identical to the no-mix trace. ``tenant_mix`` /
    ``tier_mix`` (:func:`parse_name_mix` pairs) draw the SLO scheduling
    fields the same way, each on its own derived stream."""
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    return list(generate_stream(
        None, n=n, mode=mode, rate_per_s=rate_per_s, seed=seed, steps=steps,
        scheduler=scheduler, burst_size=burst_size,
        burst_gap_ms=burst_gap_ms, deadline_ms=deadline_ms,
        distinct_keys=distinct_keys, gate=gate, gate_mix=gate_mix,
        tenant_mix=tenant_mix, tier_mix=tier_mix, zipf_s=zipf_s,
        zipf_universe=zipf_universe, diurnal=diurnal))


def stream_with_cancels(stream, seed: int, rate: float):
    """Streaming form of :func:`with_cancels` — same semantics (each
    seeded victim is cancelled right after the next arrival), same derived
    RNG stream, O(1) memory."""
    import numpy as np

    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"cancel rate must be in [0, 1], got {rate}")
    rng = np.random.RandomState(seed ^ 0x5CA1AB1E)
    pending_cancel = None
    for req in stream:
        yield req
        if pending_cancel is not None:
            yield {"cancel": pending_cancel}
            pending_cancel = None
        if rng.random_sample() < rate:
            pending_cancel = req["request_id"]


def with_cancels(trace: List[dict], seed: int, rate: float) -> List[dict]:
    """Interleave seeded ``{"cancel": id}`` markers: each victim (drawn
    with probability ``rate``) is cancelled right after the *next* arrival,
    so it is in the queue but (usually) not yet dispatched. The last
    request has no later arrival to ride and is never a victim. Cancel
    markers carry no ``arrival_ms`` — the serve trace parser times them by
    stream position. (The materialized form of
    :func:`stream_with_cancels`.)"""
    return list(stream_with_cancels(iter(trace), seed, rate))


def fault_plan_dict(trace: List[dict], seed: int, rate: float,
                    kinds=("transient", "poison", "nan")) -> dict:
    """A ``serve.chaos.FaultPlan`` (as its JSON dict) drawn over the
    trace's request ids — same seed + same trace ⇒ byte-identical plan."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from p2p_tpu.serve.chaos import FaultPlan

    rids = [r["request_id"] for r in trace if "request_id" in r]
    return FaultPlan.generate(seed, rids, rate=rate,
                              kinds=tuple(kinds)).to_dict()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--duration-ms", type=float, default=None, metavar="MS",
                    help="streaming long-trace mode: emit requests until "
                         "arrival_ms exceeds this virtual-clock horizon, "
                         "one line at a time (nothing materialized — soak "
                         "traces can be hours-equivalent). Overrides --n; "
                         "incompatible with --fault-rate, whose plan needs "
                         "the finite id list")
    ap.add_argument("--mode", choices=("poisson", "burst"), default="poisson")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="poisson arrival rate, requests/second")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--scheduler", choices=("ddim", "plms", "dpm"),
                    default="ddim")
    ap.add_argument("--burst-size", type=int, default=8)
    ap.add_argument("--burst-gap-ms", type=float, default=500.0)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--distinct-keys", type=int, default=1,
                    help="spread the trace over this many step-counts "
                         "(distinct compile keys) for cache-pressure runs")
    ap.add_argument("--gate", default=None,
                    help="phase-gate spec stamped on every request "
                         "('auto', a fraction, or a step index)")
    ap.add_argument("--gate-mix", default=None, metavar="SPEC",
                    help="weighted gate distribution drawn per request "
                         "from the trace seed, e.g. '0.5:2,off:1,auto:1' "
                         "(value ':' weight; 'off'/'none' = ungated; bare "
                         "value = weight 1). Overrides --gate; exercises "
                         "the serve layer's phase hand-off and "
                         "mixed-phase packing")
    ap.add_argument("--tenant-mix", default=None, metavar="SPEC",
                    help="weighted tenant distribution drawn per request "
                         "on its own derived RNG stream, e.g. "
                         "'acme:2,globex:1,off:1' ('off'/'none' = no "
                         "tenant field; bare value = weight 1) — "
                         "arrivals/seeds stay byte-identical to the "
                         "mix-less trace (the --gate-mix discipline)")
    ap.add_argument("--tier-mix", default=None, metavar="SPEC",
                    help="weighted SLO-tier distribution drawn per "
                         "request on its own derived RNG stream, e.g. "
                         "'premium:1,best_effort:3' (tiers: premium, "
                         "standard, best_effort; 'off'/'none' = no tier "
                         "field)")
    ap.add_argument("--zipf", type=float, default=None, metavar="S",
                    help="popularity mode (ISSUE 13): draw each request's "
                         "identity — prompt pair + seed, i.e. its semantic-"
                         "cache content — from a Zipf(S) rank distribution "
                         "over --zipf-universe distinct identities, on its "
                         "own derived RNG stream (arrivals/deadlines stay "
                         "byte-identical to the non-zipf trace)")
    ap.add_argument("--zipf-universe", type=int, default=32, metavar="K",
                    help="distinct request identities under --zipf "
                         "(default 32)")
    ap.add_argument("--diurnal", default=None, nargs="?", const="on",
                    metavar="on|k=v,...",
                    help="diurnal traffic mode (ISSUE 19): modulate the "
                         "poisson rate through a sinusoidal day-curve — "
                         "'on' or a comma list over period_ms/low/high "
                         "(defaults 4000/0.25/4). Deterministic multiplier "
                         "on each drawn gap: arrivals are byte-identical "
                         "to the flat trace when the mode is off; gives "
                         "elastic-serving drills realistic pressure "
                         "swings (poisson only)")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="interleave seeded {'cancel': id} markers at this "
                         "per-request probability (each victim cancelled "
                         "one arrival after admission)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="emit a chaos FaultPlan JSON drawing a fault per "
                         "request id at this probability "
                         "(see --fault-plan-out)")
    ap.add_argument("--fault-kinds", default="transient,poison,nan",
                    help="comma list of fault kinds the plan draws from "
                         "(transient, poison, fatal, hang, nan)")
    ap.add_argument("--fault-plan-out", default=None,
                    help="where to write the FaultPlan JSON (default: "
                         "<--out>.faults.json; required with --fault-rate "
                         "when the trace goes to stdout)")
    ap.add_argument("--out", default=None,
                    help="write the JSONL trace here (default: stdout)")
    args = ap.parse_args(argv)

    gate = args.gate
    if isinstance(gate, str) and gate != "auto":
        gate = float(gate) if "." in gate else int(gate)
    gate_mix = parse_gate_mix(args.gate_mix) if args.gate_mix else None
    tenant_mix = (parse_name_mix(args.tenant_mix, "tenant mix")
                  if args.tenant_mix else None)
    tier_mix = (parse_name_mix(args.tier_mix, "tier mix")
                if args.tier_mix else None)
    try:
        diurnal = (parse_diurnal(args.diurnal)
                   if args.diurnal is not None else None)
    except ValueError as e:
        ap.error(str(e))
    if args.duration_ms is not None:
        if args.fault_rate > 0:
            ap.error("--fault-rate needs a finite --n trace (the fault "
                     "plan draws over the complete request-id list)")
        stream = generate_stream(
            args.duration_ms, mode=args.mode, rate_per_s=args.rate,
            seed=args.seed, steps=args.steps, scheduler=args.scheduler,
            burst_size=args.burst_size, burst_gap_ms=args.burst_gap_ms,
            deadline_ms=args.deadline_ms, distinct_keys=args.distinct_keys,
            gate=gate, gate_mix=gate_mix, tenant_mix=tenant_mix,
            tier_mix=tier_mix, zipf_s=args.zipf,
            zipf_universe=args.zipf_universe, diurnal=diurnal)
        if args.cancel_rate > 0:
            stream = stream_with_cancels(stream, args.seed,
                                         args.cancel_rate)
        out = open(args.out, "w") if args.out else sys.stdout
        try:
            for req in stream:
                out.write(json.dumps(req) + "\n")
        finally:
            if out is not sys.stdout:
                out.close()
        return 0
    trace = generate_trace(
        args.n, mode=args.mode, rate_per_s=args.rate, seed=args.seed,
        steps=args.steps, scheduler=args.scheduler,
        burst_size=args.burst_size, burst_gap_ms=args.burst_gap_ms,
        deadline_ms=args.deadline_ms, distinct_keys=args.distinct_keys,
        gate=gate, gate_mix=gate_mix, tenant_mix=tenant_mix,
        tier_mix=tier_mix, zipf_s=args.zipf,
        zipf_universe=args.zipf_universe, diurnal=diurnal)
    if args.fault_rate > 0:
        plan_path = args.fault_plan_out or (
            args.out and args.out + ".faults.json")
        if not plan_path:
            ap.error("--fault-rate needs --fault-plan-out (or --out)")
        plan = fault_plan_dict(trace, args.seed, args.fault_rate,
                               kinds=[k for k in
                                      args.fault_kinds.split(",") if k])
        with open(plan_path, "w") as f:
            json.dump(plan, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {plan_path} "
              f"({len(plan['by_request'])} faulted ids)", file=sys.stderr)
    if args.cancel_rate > 0:
        trace = with_cancels(trace, args.seed, args.cancel_rate)
    out = open(args.out, "w") if args.out else sys.stdout
    try:
        for req in trace:
            out.write(json.dumps(req) + "\n")
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
