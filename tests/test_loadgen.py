"""tools/loadgen.py: deterministic arrival traces for the serve layer."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _loadgen():
    spec = importlib.util.spec_from_file_location(
        "loadgen", os.path.join(REPO, "tools", "loadgen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_same_seed_same_trace_different_seed_differs():
    lg = _loadgen()
    a = lg.generate_trace(16, seed=7, steps=4)
    b = lg.generate_trace(16, seed=7, steps=4)
    c = lg.generate_trace(16, seed=8, steps=4)
    assert a == b
    assert a != c


def test_poisson_trace_sorted_and_valid_requests():
    from p2p_tpu.serve import Request

    lg = _loadgen()
    trace = lg.generate_trace(32, mode="poisson", rate_per_s=20.0, seed=0,
                              steps=4)
    arrivals = [d["arrival_ms"] for d in trace]
    assert arrivals == sorted(arrivals)
    assert arrivals[0] == 0.0
    assert len({d["request_id"] for d in trace}) == 32
    # Every line is a valid serve request (schema round trip), and the whole
    # trace shares one compile key's worth of static config.
    reqs = [Request.from_dict(d) for d in trace]
    assert {(r.steps, r.scheduler, r.mode) for r in reqs} == {(4, "ddim",
                                                              "replace")}
    # Mean interarrival tracks 1000/rate (loose: it's one seeded sample).
    mean_gap = arrivals[-1] / (len(arrivals) - 1)
    assert 20.0 < mean_gap < 120.0


def test_burst_trace_groups_arrivals():
    lg = _loadgen()
    trace = lg.generate_trace(12, mode="burst", burst_size=4,
                              burst_gap_ms=500.0, seed=0, steps=4)
    arrivals = [d["arrival_ms"] for d in trace]
    assert arrivals == [0.0] * 4 + [500.0] * 4 + [1000.0] * 4


def test_distinct_keys_and_optional_fields():
    lg = _loadgen()
    trace = lg.generate_trace(8, seed=0, steps=4, distinct_keys=2,
                              deadline_ms=250.0, gate="auto")
    assert {d["steps"] for d in trace} == {4, 5}
    assert all(d["deadline_ms"] == 250.0 and d["gate"] == "auto"
               for d in trace)


def test_gate_mix_schema_and_determinism():
    """--gate-mix pins (ISSUE 6): the mix draws per-request gates from the
    trace seed without perturbing arrivals or seeds, 'off' entries omit
    the field entirely, and the spec parser round-trips the documented
    syntax."""
    lg = _loadgen()
    assert lg.parse_gate_mix("0.5:2,off:1,auto:1") == [
        (0.5, 2.0), (None, 1.0), ("auto", 1.0)]
    assert lg.parse_gate_mix("0.5") == [(0.5, 1.0)]      # bare = weight 1
    assert lg.parse_gate_mix("3:1") == [(3, 1.0)]        # int = step index
    mix = lg.parse_gate_mix("0.5:1,off:1")
    base = lg.generate_trace(32, seed=5, steps=4)
    mixed = lg.generate_trace(32, seed=5, steps=4, gate_mix=mix)
    again = lg.generate_trace(32, seed=5, steps=4, gate_mix=mix)
    assert mixed == again                                 # deterministic
    # Arrivals and seeds are byte-identical to the no-mix trace: the gate
    # draws ride the same RNG *after* each seed draw.
    for b, m in zip(base, mixed):
        assert {k: v for k, v in m.items() if k != "gate"} == b
    gates = [m.get("gate") for m in mixed]
    assert set(gates) == {0.5, None}                      # both sides drawn
    # An all-'off' mix is the preserved default: no gate field anywhere.
    off = lg.generate_trace(8, seed=5, steps=4,
                            gate_mix=lg.parse_gate_mix("off"))
    assert off == lg.generate_trace(8, seed=5, steps=4)
    # A gated trace is valid serve schema and round-trips prepare()'s gate.
    from p2p_tpu.serve import Request

    reqs = [Request.from_dict(d) for d in mixed]
    assert {r.gate for r in reqs} == {0.5, None}
    with pytest.raises(ValueError, match="weight must be positive"):
        lg.parse_gate_mix("0.5:0")
    with pytest.raises(ValueError, match="empty gate mix"):
        lg.parse_gate_mix(" , ")


def test_tenant_and_tier_mix_schema_and_determinism():
    """ISSUE 12 satellite pin: --tenant-mix/--tier-mix draw the SLO
    scheduling fields per request on SEPARATE derived RNG streams, so a
    mixed trace is byte-identical to the mix-less trace everywhere but
    its own fields — and the two mixes never perturb each other or the
    gate draws."""
    lg = _loadgen()
    assert lg.parse_name_mix("acme:2,globex:1,off:1") == [
        ("acme", 2.0), ("globex", 1.0), (None, 1.0)]
    assert lg.parse_name_mix("premium") == [("premium", 1.0)]
    tenant_mix = lg.parse_name_mix("acme:1,globex:1")
    tier_mix = lg.parse_name_mix("premium:1,best_effort:3")
    base = lg.generate_trace(32, seed=5, steps=4)
    mixed = lg.generate_trace(32, seed=5, steps=4, tenant_mix=tenant_mix,
                              tier_mix=tier_mix)
    assert mixed == lg.generate_trace(32, seed=5, steps=4,
                                      tenant_mix=tenant_mix,
                                      tier_mix=tier_mix)  # deterministic
    # Arrivals/seeds byte-identical to the mix-less trace.
    for b, m in zip(base, mixed):
        assert {k: v for k, v in m.items()
                if k not in ("tenant", "tier")} == b
    assert {m["tenant"] for m in mixed} == {"acme", "globex"}
    assert {m["tier"] for m in mixed} == {"premium", "best_effort"}
    # Each mix rides its OWN stream: adding the tier mix never changes
    # the tenant draws (and vice versa), and neither perturbs gate draws.
    tenant_only = lg.generate_trace(32, seed=5, steps=4,
                                    tenant_mix=tenant_mix)
    assert [m["tenant"] for m in mixed] == \
        [t["tenant"] for t in tenant_only]
    gmix = lg.parse_gate_mix("0.5:1,off:1")
    gated = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix)
    all_three = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix,
                                  tenant_mix=tenant_mix, tier_mix=tier_mix)
    assert [m.get("gate") for m in all_three] == \
        [g.get("gate") for g in gated]
    # 'off' entries omit the field entirely; an all-off mix is the
    # preserved default trace, byte-identical.
    off = lg.generate_trace(8, seed=5, steps=4,
                            tenant_mix=lg.parse_name_mix("off"),
                            tier_mix=lg.parse_name_mix("none"))
    assert off == lg.generate_trace(8, seed=5, steps=4)
    # The streaming form draws in the same per-request order (the
    # seed-stable prefix contract).
    import itertools

    assert list(itertools.islice(
        lg.generate_stream(None, seed=5, steps=4, tenant_mix=tenant_mix,
                           tier_mix=tier_mix), 16)) == mixed[:16]
    # A mixed trace is valid serve schema end to end.
    from p2p_tpu.serve import Request

    reqs = [Request.from_dict(d) for d in mixed]
    assert {r.tier for r in reqs} <= {"premium", "standard", "best_effort"}
    with pytest.raises(ValueError, match="weight must be positive"):
        lg.parse_name_mix("acme:0")
    with pytest.raises(ValueError, match="empty"):
        lg.parse_name_mix(" , ")


def test_zipf_popularity_mode_discipline_and_prefix_stability():
    """ISSUE 13 satellite pin: --zipf draws each request's IDENTITY
    (prompt pair + seed — its semantic-cache content) from a Zipf(s) rank
    distribution on SEPARATE derived RNG streams, so arrivals, deadlines
    and every other mix stay byte-identical to the non-zipf trace — and
    the streaming prefix contract holds under it."""
    import itertools

    lg = _loadgen()
    base = lg.generate_trace(48, seed=5, steps=4, deadline_ms=400.0)
    zipf = lg.generate_trace(48, seed=5, steps=4, deadline_ms=400.0,
                             zipf_s=1.1, zipf_universe=8)
    assert zipf == lg.generate_trace(48, seed=5, steps=4,
                                     deadline_ms=400.0, zipf_s=1.1,
                                     zipf_universe=8)  # deterministic
    # Only the identity fields (prompt/target/seed) may differ.
    for b, z in zip(base, zipf):
        assert {k: v for k, v in z.items()
                if k not in ("prompt", "target", "seed")} == \
            {k: v for k, v in b.items()
             if k not in ("prompt", "target", "seed")}
    # Popularity is real: 8 identities over 48 requests repeat, skewed —
    # the head identity strictly dominates a uniform share.
    idents = [(z["prompt"], z["seed"]) for z in zipf]
    assert len(set(idents)) <= 8 < len(idents)
    head = max(set(idents), key=idents.count)
    assert idents.count(head) > len(idents) / 8
    # Identity table is horizon-independent (prefix stability): the same
    # identities appear whatever n, and the stream form matches.
    assert lg.generate_trace(16, seed=5, steps=4, deadline_ms=400.0,
                             zipf_s=1.1, zipf_universe=8) == zipf[:16]
    assert list(itertools.islice(
        lg.generate_stream(None, seed=5, steps=4, deadline_ms=400.0,
                           zipf_s=1.1, zipf_universe=8), 24)) == zipf[:24]
    # The zipf stream never perturbs the other mixes (own-stream rule).
    gmix = lg.parse_gate_mix("0.5:1,off:1")
    gated = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix)
    both = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix,
                             zipf_s=1.1, zipf_universe=8)
    assert [m.get("gate") for m in both] == [g.get("gate") for g in gated]
    # A zipf trace is valid serve schema end to end.
    from p2p_tpu.serve import Request

    assert all(Request.from_dict(d) for d in zipf)
    with pytest.raises(ValueError, match="zipf s must be positive"):
        lg.generate_trace(4, zipf_s=0.0)
    with pytest.raises(ValueError, match="zipf universe"):
        lg.generate_trace(4, zipf_s=1.1, zipf_universe=0)


def test_diurnal_modulates_rate_without_perturbing_the_stream():
    """ISSUE 19 satellite pin: --diurnal divides each drawn poisson gap by
    a deterministic sinusoidal day-curve multiplier, so the base RNG
    stream is consumed identically — everything except arrival_ms is
    byte-identical to the flat trace, and switching the mode off restores
    the flat trace byte-for-byte (the docstring's claim)."""
    import itertools

    lg = _loadgen()
    assert lg.parse_diurnal("on") == lg.parse_diurnal("") == \
        lg.parse_diurnal("default") == \
        {"period_ms": 4000.0, "low": 0.25, "high": 4.0}
    assert lg.parse_diurnal("period_ms=2000,high=8") == \
        {"period_ms": 2000.0, "low": 0.25, "high": 8.0}
    flat = lg.generate_trace(64, mode="poisson", rate_per_s=40.0, seed=5,
                             steps=4)
    day = lg.generate_trace(64, mode="poisson", rate_per_s=40.0, seed=5,
                            steps=4, diurnal=lg.parse_diurnal("on"))
    assert day == lg.generate_trace(64, mode="poisson", rate_per_s=40.0,
                                    seed=5, steps=4,
                                    diurnal=lg.parse_diurnal("on"))
    # diurnal=None IS the flat trace (off restores bytes), and with the
    # mode on only arrival_ms may differ.
    assert flat == lg.generate_trace(64, mode="poisson", rate_per_s=40.0,
                                     seed=5, steps=4, diurnal=None)
    for f, d in zip(flat, day):
        assert {k: v for k, v in d.items() if k != "arrival_ms"} == \
            {k: v for k, v in f.items() if k != "arrival_ms"}
    # The modulation is real and bounded: each diurnal gap is the flat
    # gap divided by the curve value, which lives in [low, high] — and a
    # trace spanning a full 4 s virtual day visits both ends of it.
    fgaps = [b["arrival_ms"] - a["arrival_ms"]
             for a, b in zip(flat, flat[1:])]
    dgaps = [b["arrival_ms"] - a["arrival_ms"] for a, b in zip(day, day[1:])]
    mults = [f / d for f, d in zip(fgaps, dgaps) if d > 0]
    assert all(0.25 - 1e-9 <= m <= 4.0 + 1e-9 for m in mults)
    assert max(mults) / min(mults) > 4.0
    # The phase offset rides its own derived stream: a different seed
    # peaks at a different time of "day" (different multiplier at t=0).
    flat9 = lg.generate_trace(64, mode="poisson", rate_per_s=40.0, seed=9,
                              steps=4)
    day9 = lg.generate_trace(64, mode="poisson", rate_per_s=40.0, seed=9,
                             steps=4, diurnal=lg.parse_diurnal("on"))
    m5 = fgaps[0] / dgaps[0]
    m9 = (flat9[1]["arrival_ms"] - flat9[0]["arrival_ms"]) / \
        (day9[1]["arrival_ms"] - day9[0]["arrival_ms"])
    assert abs(m5 - m9) > 1e-6
    # Own-stream discipline: diurnal never perturbs the mix draws.
    gmix = lg.parse_gate_mix("0.5:1,off:1")
    gated = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix)
    both = lg.generate_trace(32, seed=5, steps=4, gate_mix=gmix,
                             diurnal=lg.parse_diurnal("on"))
    assert [m.get("gate") for m in both] == [g.get("gate") for g in gated]
    # The streaming form rides the same per-request draw order (the
    # seed-stable prefix contract).
    assert list(itertools.islice(
        lg.generate_stream(None, mode="poisson", rate_per_s=40.0, seed=5,
                           steps=4, diurnal=lg.parse_diurnal("on")),
        32)) == day[:32]
    # Validation: burst mode has no rate to modulate; parse errors name
    # the offending field.
    with pytest.raises(ValueError, match="no rate to modulate"):
        lg.generate_trace(4, mode="burst", steps=4,
                          diurnal=lg.parse_diurnal("on"))
    with pytest.raises(ValueError, match="expects 'on' or 'k=v"):
        lg.parse_diurnal("fast")
    with pytest.raises(ValueError, match="unknown --diurnal field"):
        lg.parse_diurnal("speed=2")
    with pytest.raises(ValueError, match="period_ms must be positive"):
        lg.parse_diurnal("period_ms=0")
    with pytest.raises(ValueError, match="0 < low <= high"):
        lg.parse_diurnal("low=2,high=1")


def test_cross_tool_seed_stability_pins():
    """ISSUE 13 bugfix satellite: the PR-8 per-request draw-order change
    silently shifted every tool's seeded workload once — this pin makes
    the next loadgen RNG refactor loud instead. Audit of every in-repo
    trace constructor (chaos_drill.standard_trace / slo_overload_drill /
    cache_parity_drill, tools/soak.py): all ride
    ``generate_trace``/``generate_stream``, which share one per-request
    draw path (``generate_trace`` IS ``list(generate_stream(n=K))``), so
    pinning (a) the tool-level trace bytes for the drills' own default
    seeds and (b) the tool-args equivalence is sufficient: (a) breaks on
    any RNG/draw-order change, (b) breaks if a tool's workload drifts
    from the documented invocation."""
    import hashlib

    lg = _loadgen()

    def digest(obj):
        return hashlib.sha256(
            json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]

    spec = importlib.util.spec_from_file_location(
        "chaos_drill", os.path.join(REPO, "tools", "chaos_drill.py"))
    drill = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drill)

    # chaos_drill.standard_trace (quality gate fault_drill / bench
    # resilience): trace AND fault plan, at the drill's default seed.
    trace, plan = drill.standard_trace()
    assert digest(trace) == "6e6282b1b8b0a390"
    assert digest(plan.to_dict()) == "90d33cc61ce2c5d6"

    # tools/soak.py's stream (run_soak defaults): 30s virtual horizon at
    # 20 req/s, seed 0, the 0.5:1,off:1 gate mix.
    soak = list(lg.generate_stream(
        30000.0, mode="poisson", rate_per_s=20.0, seed=0, steps=4,
        gate_mix=lg.parse_gate_mix("0.5:1,off:1")))
    assert len(soak) == 608
    assert digest(soak) == "14b4eb6b30c3d634"

    # cache_parity_drill's zipf trace (quality gate cache_parity / bench
    # serve.cache): the --zipf 1.1 repeat-heavy workload at its defaults.
    zipf = lg.generate_trace(32, mode="poisson", rate_per_s=10.0, seed=13,
                             steps=3, gate=0.5, zipf_s=1.1,
                             zipf_universe=16)
    assert digest(zipf) == "4c50f6ead3fe43e2"
    # ...and the drill really runs exactly that workload (args drift pin).
    import inspect

    sig = inspect.signature(drill.cache_parity_drill)
    assert sig.parameters["n"].default == 32
    assert sig.parameters["seed"].default == 13
    assert sig.parameters["steps"].default == 3
    assert sig.parameters["zipf_s"].default == 1.1
    assert sig.parameters["zipf_universe"].default == 16
    assert sig.parameters["rate_per_s"].default == 10.0


def test_validation_errors():
    lg = _loadgen()
    with pytest.raises(ValueError, match="n must be"):
        lg.generate_trace(0)
    with pytest.raises(ValueError, match="mode"):
        lg.generate_trace(4, mode="ramp")
    with pytest.raises(ValueError, match="rate"):
        lg.generate_trace(4, rate_per_s=0.0)


def test_cli_writes_jsonl(tmp_path):
    lg = _loadgen()
    out = tmp_path / "trace.jsonl"
    assert lg.main(["--n", "6", "--mode", "poisson", "--rate", "50",
                    "--seed", "3", "--steps", "4", "--out", str(out)]) == 0
    lines = [json.loads(l) for l in open(out)]
    assert len(lines) == 6
    assert lines == lg.generate_trace(6, mode="poisson", rate_per_s=50.0,
                                      seed=3, steps=4)


def test_stream_prefix_is_seed_stable(tmp_path):
    """ISSUE 9 satellite pin: the streaming long-trace mode draws its RNG
    per request, so the first K requests are byte-identical to the finite
    --n K trace — whatever the horizon (or no horizon at all)."""
    import itertools

    lg = _loadgen()
    finite = lg.generate_trace(24, mode="poisson", rate_per_s=30.0, seed=9,
                               steps=4)
    prefix = list(itertools.islice(
        lg.generate_stream(None, mode="poisson", rate_per_s=30.0, seed=9,
                           steps=4), 24))
    assert prefix == finite
    # A duration-bounded stream is a prefix of the unbounded one.
    horizon = lg.generate_trace(24, mode="poisson", rate_per_s=30.0,
                                seed=9, steps=4)[11]["arrival_ms"]
    bounded = list(lg.generate_stream(horizon, mode="poisson",
                                      rate_per_s=30.0, seed=9, steps=4))
    assert bounded == finite[:len(bounded)]
    assert len(bounded) >= 12
    assert all(r["arrival_ms"] <= horizon for r in bounded)
    # Gate-mix and burst mode ride the same per-request draw order.
    mix = lg.parse_gate_mix("0.5:1,off:1")
    assert list(itertools.islice(
        lg.generate_stream(None, seed=5, steps=4, gate_mix=mix), 16)) == \
        lg.generate_trace(16, seed=5, steps=4, gate_mix=mix)
    assert list(itertools.islice(
        lg.generate_stream(None, mode="burst", seed=2, steps=4,
                           burst_size=4), 12)) == \
        lg.generate_trace(12, mode="burst", seed=2, steps=4, burst_size=4)


def test_stream_with_cancels_matches_finite_form():
    lg = _loadgen()
    trace = lg.generate_trace(20, seed=7, steps=4)
    assert list(lg.stream_with_cancels(iter(trace), 7, 0.3)) == \
        lg.with_cancels(trace, 7, 0.3)


def test_cli_duration_ms_streams_and_rejects_fault_rate(tmp_path):
    lg = _loadgen()
    out = tmp_path / "soak.jsonl"
    assert lg.main(["--duration-ms", "2000", "--rate", "20", "--seed", "3",
                    "--steps", "4", "--out", str(out)]) == 0
    lines = [json.loads(l) for l in open(out)]
    assert lines, "the horizon produced requests"
    assert all(r["arrival_ms"] <= 2000 for r in lines)
    assert lines == lg.generate_trace(len(lines), rate_per_s=20.0, seed=3,
                                      steps=4)
    with pytest.raises(SystemExit):
        lg.main(["--duration-ms", "2000", "--fault-rate", "0.5",
                 "--out", str(out)])
