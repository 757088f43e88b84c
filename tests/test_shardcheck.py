"""shardcheck tests (ISSUE 11): the StableHLO/HLO walker's parsing on
planted programs, seeded verdict-flips for every new contract class
(undeclared all-gather via an unsharded-operand constraint, stale
declaration, planted host callback / hidden resharding), the
clean-on-HEAD sweep over the real mesh canonical programs, and the report
integration that carries the per-program bytes-per-step comms table.

The planted programs are tiny jits (sub-second compiles); the real-program
leg compiles the dp=1 mesh canonical set in tier-1 and sweeps the full
dp ∈ {1, 2, 4} axis under the ``slow`` marker (the jaxcheck CLI and the
quality gate run it too)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_tpu.analysis import report as report_mod
from p2p_tpu.analysis import shlo_walk
from p2p_tpu.analysis.collectives import (DECLARED_COLLECTIVES, MeshProgram,
                                          check_collectives, mesh_dps)


def _mesh2():
    return Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1), ("dp", "tp"))


def _forced_replication_lowered():
    """THE seeded bug shape: a dp-sharded operand whose result is forced
    replicated — the partitioner must insert an all-gather."""
    mesh = _mesh2()
    rep = NamedSharding(mesh, P())

    def f(x):
        return jax.lax.with_sharding_constraint(x * 2.0, rep)

    x = jax.device_put(jnp.zeros((4, 8, 8, 16)),
                       NamedSharding(mesh, P("dp")))
    return jax.jit(f).lower(x)


def _planted(name, lowered, steps=3, dp=2, lanes=2):
    return MeshProgram(name=name, dp=dp, lanes=lanes, steps=steps,
                       stablehlo=lowered.as_text(),
                       hlo=lowered.compile().as_text())


# ---------------------------------------------------------------------------
# shlo_walk parsing on planted programs
# ---------------------------------------------------------------------------


def test_walker_finds_forced_replication_all_gather():
    low = _forced_replication_lowered()
    ops = shlo_walk.collective_ops(low.compile().as_text())
    assert [o.kind for o in ops] == ["all-gather"]
    op = ops[0]
    assert op.shape == (4, 8, 8, 16) and op.dtype == "f32"
    assert op.group_size == 2 and not op.per_step
    # 4*8*8*16 f32 = 16384B payload; ring all-gather moves (g-1)/g of it.
    assert op.payload_bytes == 16384 and op.bytes_moved == 8192
    # ...and the *intent* is visible pre-partitioning as a replicating
    # sharding constraint on the StableHLO side.
    changes = shlo_walk.sharding_changes(low.as_text())
    assert [c.target for c in changes] == ["sharding_constraint"]
    assert changes[0].forces_replication
    assert changes[0].result_type == "4x8x8x16xf32"


def test_walker_attributes_scan_body_collectives_per_step():
    mesh = _mesh2()

    def step(c, x):
        return c + jax.lax.psum(x.sum(), "dp"), x

    def scanner(xs):
        out, _ = jax.lax.scan(step, jnp.float32(0), xs)
        return out

    sf = jax.shard_map(scanner, mesh=mesh, in_specs=P(None, "dp"),
                       out_specs=P(), check_vma=False)
    low = jax.jit(sf).lower(jnp.zeros((3, 4, 16)))
    # The shard_map boundary is sharding intent too, and not a replication.
    changes = shlo_walk.sharding_changes(low.as_text())
    assert [(c.target, c.forces_replication) for c in changes] == [
        ("manual_computation", False)]
    hlo = low.compile().as_text()
    ops = shlo_walk.collective_ops(hlo)
    assert [(o.kind, o.per_step) for o in ops] == [("all-reduce", True)]
    sig = shlo_walk.collective_signature(ops)
    assert sig["ops"] == {"all-reduce": 1}
    assert sig["bytes_per_step"] > 0 and sig["bytes_once"] == 0


def test_walker_finds_host_boundary_ops():
    def noisy(x):
        jax.debug.callback(lambda v: None, x)
        return x * 1.0

    hlo = jax.jit(noisy).lower(jnp.zeros((4,))).compile().as_text()
    assert any("callback" in h for h in shlo_walk.host_boundary_ops(hlo))

    from jax.experimental import io_callback

    def cb(x):
        io_callback(lambda v: None, None, x)
        return x + 1

    low = jax.jit(cb).lower(jnp.zeros((4,)))
    # The callback is visible in BOTH text forms (custom_call @...callback
    # in StableHLO, custom-call target in compiled HLO).
    assert any("callback" in h for h in
               shlo_walk.host_boundary_ops(low.as_text()))
    assert any("callback" in h for h in
               shlo_walk.host_boundary_ops(low.compile().as_text()))
    # A clean program reports none.
    clean = jax.jit(lambda x: x * 2).lower(jnp.zeros((4,)))
    assert shlo_walk.host_boundary_ops(clean.as_text()) == []
    assert shlo_walk.host_boundary_ops(clean.compile().as_text()) == []


def test_walker_finds_reduce_scatter():
    # XLA rewrites all-reduce-into-sharded-consumer as reduce-scatter:
    # missing this kind would blind the budget to real traffic.
    mesh = _mesh2()

    def f(x):
        return jax.lax.psum_scatter(x, "dp", tiled=True)

    sf = jax.shard_map(f, mesh=mesh, in_specs=P(None, "dp"),
                       out_specs=P("dp"), check_vma=False)
    hlo = jax.jit(sf).lower(jnp.zeros((4, 8))).compile().as_text()
    ops = shlo_walk.collective_ops(hlo)
    assert [o.kind for o in ops] == ["reduce-scatter"]
    # Result type is the SHARD (2x4 f32 = 32B); each participant ships
    # every shard but its own: (g-1) * shard.
    assert ops[0].payload_bytes == 32 and ops[0].bytes_moved == 32


def test_walker_folds_async_collective_start_forms():
    # GPU/TPU pipelines emit `all-gather-start`/`-done` pairs; the -start
    # carries the traffic (counted once, payload = the result element of
    # the aliasing tuple), the -done is a wait (not counted).
    line = ("%all-gather-start = (f32[2,8]{1,0}, f32[4,8]{1,0}) "
            "all-gather-start(f32[2,8]{1,0} %p), channel_id=1, "
            "replica_groups=[1,2]<=[2], dimensions={0}")
    done = ("%all-gather-done = f32[4,8]{1,0} "
            "all-gather-done((f32[2,8]{1,0}, f32[4,8]{1,0}) "
            "%all-gather-start)")
    hlo = "ENTRY %main (p: f32[2,8]) -> f32[4,8] {\n  " \
        + line + "\n  " + done + "\n}\n"
    ops = shlo_walk.collective_ops(hlo)
    assert [(o.kind, o.payload_bytes) for o in ops] == [("all-gather", 128)]


def test_ring_cost_model():
    # all-reduce = reduce-scatter + all-gather; degenerate groups are free.
    assert shlo_walk.cost_bytes("all-reduce", 1000, 2) == 1000
    assert shlo_walk.cost_bytes("all-gather", 1000, 2) == 500
    assert shlo_walk.cost_bytes("all-gather", 1000, 4) == 750
    assert shlo_walk.cost_bytes("reduce-scatter", 1000, 4) == 3000
    assert shlo_walk.cost_bytes("collective-permute", 1000, 4) == 1000
    assert shlo_walk.cost_bytes("all-reduce", 1000, 1) == 0


def test_replica_group_parsing_all_spellings():
    assert shlo_walk._group_size("replica_groups={{0,1},{2,3}}") == 2
    assert shlo_walk._group_size("replica_groups=[1,2]<=[2]") == 2
    assert shlo_walk._group_size("replica_groups=[2,4]<=[8]") == 4
    assert shlo_walk._group_size("no groups here") == 1
    # replica_groups={} = ONE group of every partition (sized from the
    # HloModule header), not a degenerate free group.
    assert shlo_walk._group_size("replica_groups={}", num_partitions=8) == 8
    # collective-permute has pairs, not groups: any non-self pair is real
    # traffic; all-self pairs (or none) are degenerate.
    assert shlo_walk._group_size(
        "source_target_pairs={{0,1},{1,0}}") == 2
    assert shlo_walk._group_size("source_target_pairs={{0,0}}") == 1


def test_permute_and_all_device_groups_are_priced_not_zeroed():
    # The two spellings a naive group parser prices at 0 bytes: a permute
    # (source_target_pairs) and an all-devices all-reduce
    # (replica_groups={}) — both must land in the budget.
    hlo = (
        "HloModule jit_f, num_partitions=4\n"
        "\n"
        "ENTRY %main (p: f32[4,8]) -> f32[4,8] {\n"
        "  %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %p), "
        "channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n"
        "  %ar = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %cp), "
        "channel_id=2, replica_groups={}, to_apply=%add\n"
        "}\n")
    ops = {o.kind: o for o in shlo_walk.collective_ops(hlo)}
    assert ops["collective-permute"].bytes_moved == 128      # full payload
    assert ops["all-reduce"].group_size == 4
    assert ops["all-reduce"].bytes_moved == 192              # 2*(3/4)*128


def test_per_step_attribution_covers_all_conditional_branches():
    # A collective inside the SECOND branch of a conditional in a while
    # body is still per-step (branch_computations lists every member).
    hlo = (
        "HloModule jit_f, num_partitions=2\n"
        "\n"
        "%b0 (p0: f32[4]) -> f32[4] {\n"
        "  ROOT %r0 = f32[4]{0} copy(f32[4]{0} %p0)\n"
        "}\n"
        "\n"
        "%b1 (p1: f32[4]) -> f32[4] {\n"
        "  ROOT %ag = f32[4]{0} all-gather(f32[2]{0} %p1), channel_id=1, "
        "replica_groups=[1,2]<=[2], dimensions={0}\n"
        "}\n"
        "\n"
        "%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {\n"
        "  %sel = f32[4]{0} conditional(pred[] %q, f32[4]{0} %x, "
        "f32[4]{0} %y), branch_computations={%b0, %b1}\n"
        "}\n"
        "\n"
        "%cond (c: (s32[], f32[4])) -> pred[] {\n"
        "  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT\n"
        "}\n"
        "\n"
        "ENTRY %main (p: f32[4]) -> f32[4] {\n"
        "  %w = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), "
        "condition=%cond, body=%body\n"
        "}\n")
    ops = shlo_walk.collective_ops(hlo)
    assert [(o.kind, o.per_step) for o in ops] == [("all-gather", True)]


def test_async_permute_start_payload_is_the_tensor_not_the_context():
    # collective-permute-start's result tuple trails u32[] context words;
    # the payload is the largest element, not the last.
    line = ("%cps = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[]) "
            "collective-permute-start(f32[4,8]{1,0} %p), channel_id=1, "
            "source_target_pairs={{0,1},{1,0}}")
    hlo = "ENTRY %main (p: f32[4,8]) -> f32[4,8] {\n  " + line + "\n}\n"
    ops = shlo_walk.collective_ops(hlo)
    assert [(o.kind, o.payload_bytes, o.bytes_moved) for o in ops] == [
        ("collective-permute", 128, 128)]


# ---------------------------------------------------------------------------
# Seeded verdict-flips per contract class
# ---------------------------------------------------------------------------


def _clean_lowered():
    return jax.jit(lambda x: x * 2).lower(jnp.zeros((4, 8)))


def _by(results, contract, program):
    hits = [r for r in results
            if r.contract == contract and r.program == program]
    assert len(hits) == 1, [r.format() for r in results]
    return hits[0]


def test_undeclared_all_gather_is_a_hard_error():
    prog = _planted("serve/mesh-dp2", _forced_replication_lowered())
    results, table = check_collectives(
        programs=[prog], declared={"serve/mesh-dp2": {}})
    r = _by(results, "collectives-as-declared", "serve/mesh-dp2")
    assert not r.ok
    # The error names the op, shape and ring-cost bytes.
    assert "all-gather" in r.detail and "8, 8, 16" in r.detail \
        and "8192B" in r.detail
    assert table["serve/mesh-dp2"]["ops"] == {"all-gather": 1}
    assert table["serve/mesh-dp2"]["bytes_once"] == 8192
    # The same planted program also trips the resharding detector: the
    # constraint that *caused* the gather is visible as intent.
    r2 = _by(results, "no-hidden-resharding", "serve/mesh-dp2")
    assert not r2.ok and "replication" in r2.detail


def test_declared_collectives_pass_when_matching():
    prog = _planted("serve/mesh-dp2", _forced_replication_lowered())
    results, _ = check_collectives(
        programs=[prog], declared={"serve/mesh-dp2": {"all-gather": 1}})
    assert _by(results, "collectives-as-declared", "serve/mesh-dp2").ok


def test_stale_declaration_is_a_hard_error():
    prog = _planted("serve/mesh-dp2", _clean_lowered())
    results, _ = check_collectives(
        programs=[prog], declared={"serve/mesh-dp2": {"all-gather": 1}})
    r = _by(results, "collectives-as-declared", "serve/mesh-dp2")
    assert not r.ok and "stale declaration" in r.detail


def test_missing_declaration_is_a_hard_error():
    prog = _planted("serve/mesh-dp2", _clean_lowered())
    results, _ = check_collectives(programs=[prog], declared={})
    r = _by(results, "collectives-as-declared", "serve/mesh-dp2")
    assert not r.ok and "no DECLARED_COLLECTIVES entry" in r.detail


def test_stale_program_level_declaration_is_a_hard_error():
    prog = _planted("serve/mesh-dp2", _clean_lowered())
    results, _ = check_collectives(
        programs=[prog],
        declared={"serve/mesh-dp2": {}, "serve/ghost-dp2": {}})
    r = _by(results, "collectives-as-declared", "serve/ghost-dp2")
    assert not r.ok and "no canonical mesh program" in r.detail


def test_planted_callback_flips_host_boundary():
    def noisy(x):
        jax.debug.callback(lambda v: None, x)
        return x * 1.0

    prog = _planted("serve/mesh-dp2",
                    jax.jit(noisy).lower(jnp.zeros((4,))))
    results, _ = check_collectives(
        programs=[prog], declared={"serve/mesh-dp2": {}})
    r = _by(results, "no-host-boundary", "serve/mesh-dp2")
    assert not r.ok and "callback" in r.detail
    # The clean program passes the same check.
    ok = check_collectives(programs=[_planted("serve/mesh-dp2",
                                              _clean_lowered())],
                           declared={"serve/mesh-dp2": {}})[0]
    assert _by(ok, "no-host-boundary", "serve/mesh-dp2").ok


def test_planted_resharding_flips_hidden_resharding():
    # with_sharding_constraint to the SAME sharding still emits an
    # sdy.sharding_constraint: intent alone is a finding in a canonical dp
    # program (nothing may re-spec a tensor mid-program).
    mesh = _mesh2()
    shd = NamedSharding(mesh, P("dp"))

    def f(x):
        return jax.lax.with_sharding_constraint(x * 2.0, shd)

    x = jax.device_put(jnp.zeros((4, 8)), shd)
    prog = _planted("serve/mesh-dp2", jax.jit(f).lower(x))
    results, _ = check_collectives(
        programs=[prog], declared={"serve/mesh-dp2": {}})
    r = _by(results, "no-hidden-resharding", "serve/mesh-dp2")
    assert not r.ok and "sdy.sharding_constraint" in r.detail
    assert "replication" not in r.detail


# ---------------------------------------------------------------------------
# The real mesh canonical programs
# ---------------------------------------------------------------------------


def test_mesh_dps_degrades_to_available_devices():
    assert mesh_dps((1, 2, 4)) == (1, 2, 4)   # conftest forces 8 devices
    assert mesh_dps((16,)) == ()
    assert set(DECLARED_COLLECTIVES) == {
        f"serve/{stem}-dp{d}" for d in (1, 2, 4)
        for stem in ("mesh", "phase1-mesh", "phase2-mesh")}


def test_shardcheck_clean_at_dp1(tiny_pipe):
    results, table = check_collectives(tiny_pipe, dps=(1,))
    bad = [r.format() for r in results if not r.ok]
    assert not bad, bad
    assert set(table) == {"serve/mesh-dp1", "serve/phase1-mesh-dp1",
                          "serve/phase2-mesh-dp1"}
    for row in table.values():
        assert row["ops"] == {} and row["bytes_per_step"] == 0 \
            and row["bytes_once"] == 0
    kinds = {r.contract for r in results}
    assert kinds == {"collectives-as-declared", "no-hidden-resharding",
                     "no-host-boundary"}


@pytest.mark.slow
def test_shardcheck_clean_full_dp_sweep(tiny_pipe):
    """The acceptance sweep: dp ∈ {1, 2, 4}, zero findings, a budget row
    per program (the same sweep ``tools/jaxcheck.py`` runs by default)."""
    results, table = check_collectives(tiny_pipe, dps=(1, 2, 4))
    bad = [r.format() for r in results if not r.ok]
    assert not bad, bad
    assert set(table) == set(DECLARED_COLLECTIVES)
    assert all(row["bytes_per_step"] == 0 for row in table.values())


# ---------------------------------------------------------------------------
# Report integration
# ---------------------------------------------------------------------------


def test_report_carries_collective_table_and_verdict(monkeypatch):
    from p2p_tpu.analysis.contracts import ContractResult

    table = {"serve/mesh-dp2": {"dp": 2, "lanes": 2, "steps": 3,
                                "ops": {}, "bytes_per_step": 0,
                                "bytes_once": 0}}

    def fake_check(pipe=None, dps=None, **kw):
        return ([ContractResult("collectives-as-declared",
                                "serve/mesh-dp2", True, "ops {} = declared")],
                table)

    from p2p_tpu.analysis import collectives as coll_mod

    monkeypatch.setattr(coll_mod, "check_collectives", fake_check)
    monkeypatch.setattr(report_mod, "run_ast_pass",
                        lambda *a, **k: pytest.fail("ast pass must not run"))
    rep = report_mod.run_all(only="collectives")
    assert rep["ok"] is True and rep["collectives"]["table"] == table
    text = report_mod.render_text(rep)
    assert "Shardcheck pass" in text and "collective budget" in text
    assert "serve/mesh-dp2" in text
    doc = report_mod.to_json_dict(rep)
    import json

    json.dumps(doc)
    assert doc["collectives"]["table"] == table
    assert "ast" not in doc   # --only collectives really skipped pass 1


def test_report_verdict_flips_on_shardcheck_failure(monkeypatch):
    from p2p_tpu.analysis.contracts import ContractResult

    def fake_check(pipe=None, dps=None, **kw):
        return ([ContractResult(
            "collectives-as-declared", "serve/mesh-dp4", False,
            "undeclared collective(s) {'all-gather': 1}")], {})

    from p2p_tpu.analysis import collectives as coll_mod

    monkeypatch.setattr(coll_mod, "check_collectives", fake_check)
    rep = report_mod.run_all(only="collectives")
    assert rep["ok"] is False
    assert "undeclared" in report_mod.render_text(rep)


def test_run_all_rejects_unknown_section():
    with pytest.raises(ValueError, match="only must be one of"):
        report_mod.run_all(only="bogus")
