"""Pass 3 — shardcheck: collective-budget contracts over the mesh programs.

PR 9's transfer-guard test caught two implicit transfers *at runtime*;
this pass catches the same bug class at review time, on the compiler's
own evidence. Every canonical mesh serve program
(``serve/{mesh,phase1-mesh,phase2-mesh}-dpN`` at dp ∈
:data:`SHARDCHECK_DPS`) is lowered AND compiled on the CPU backend, and
three contracts are checked over the emitted text
(:mod:`.shlo_walk`):

- ``collectives-as-declared`` — the program's collective signature (the
  op-kind multiset of its post-SPMD HLO) matches
  :data:`DECLARED_COLLECTIVES`, **both directions**: an undeclared
  collective is a hard error naming the op, shape and ring-cost bytes (an
  accidental all-gather — e.g. an unsharded operand the partitioner had
  to replicate mid-program); a declared-but-absent kind (or a declaration
  for a program the sweep no longer produces) is a stale-declaration
  error. Today every dp program declares the empty multiset: dp is
  embarrassingly parallel by design (``parallel/mesh.py`` — "Collective-
  free in the sampling loop"), replicated weights and dp-replicated host
  scalars are the *declared* baseline, and everything else is a finding.
- ``no-hidden-resharding`` — the lowered StableHLO carries no
  sharding-changing ops (``sdy.sharding_constraint``,
  ``sdy.manual_computation`` boundaries): nothing in
  a canonical dp program may re-spec — least of all replicate — a
  dp-sharded tensor mid-program.
- ``no-host-boundary`` — neither text form carries a
  host-callback custom call: the mesh dispatch path never round-trips
  the host (the static twin of the ``jax.transfer_guard("disallow")``
  dispatch tests).

The per-program :func:`~.shlo_walk.collective_signature` (op multiset +
bytes-per-step / bytes-once under the ring cost model) is returned as the
comms table the report JSON carries — the budget the mp-axis work will
design against (today: all zeros, and the contract keeps it that way
until a declaration says otherwise).

Unlike the jaxpr contracts this pass pays an XLA compile (the SPMD
partitioner only runs there), ~7s per program at TINY scale; the
persistent compile cache makes repeats cheap. Like
:func:`.contracts._mesh_dp`, the dp sweep degrades to the dp values the
process has devices for — the test/CI environments force a virtual
8-device platform, a bare laptop run still checks dp=1.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from . import shlo_walk
from .contracts import ContractResult

#: The dp widths the shardcheck sweep covers when the process has the
#: devices (tools/jaxcheck.py and the test conftest force a virtual
#: 8-device CPU platform, so CI always sweeps all three).
SHARDCHECK_DPS: Tuple[int, ...] = (1, 2, 4)

#: program name -> declared collective op-kind multiset (op -> count in
#: the compiled post-SPMD HLO). The declared baseline for the dp-only
#: mesh is ZERO collectives everywhere: per-device lane buckets are
#: independent, weights are replicated once at engine start
#: (``serve.meshing.replicate_pipeline``) and host scalars stage
#: dp-replicated — so any collective the partitioner inserts is data
#: movement nobody designed. The mp-axis PR will declare its psums here
#: (and the check will then also fail if they *disappear* — a stale
#: declaration is as much a review lie as an undeclared op).
DECLARED_COLLECTIVES: Dict[str, Dict[str, int]] = {
    "serve/mesh-dp1": {},
    "serve/mesh-dp2": {},
    "serve/mesh-dp4": {},
    "serve/phase1-mesh-dp1": {},
    "serve/phase1-mesh-dp2": {},
    "serve/phase1-mesh-dp4": {},
    "serve/phase2-mesh-dp1": {},
    "serve/phase2-mesh-dp2": {},
    "serve/phase2-mesh-dp4": {},
}

_NAME_TEMPLATES = ("serve/mesh-dp{dp}", "serve/phase1-mesh-dp{dp}",
                   "serve/phase2-mesh-dp{dp}")


@dataclasses.dataclass
class MeshProgram:
    """One lowered+compiled canonical mesh program: both text forms plus
    the metadata the comms table keys on. ``steps`` is the scan length the
    per-step bytes are denominated in."""

    name: str
    dp: int
    lanes: int
    stablehlo: str
    hlo: str
    steps: int


def mesh_dps(dps: Tuple[int, ...] = SHARDCHECK_DPS) -> Tuple[int, ...]:
    """The subset of ``dps`` this process can actually mesh (same
    degradation rule as :func:`.contracts._mesh_dp`: the sweep must run
    everywhere the analyzer does)."""
    import jax

    n = len(jax.devices())
    return tuple(d for d in dps if d <= n)


def lower_mesh_programs(pipe=None,
                        dps: Tuple[int, ...] = SHARDCHECK_DPS
                        ) -> List[MeshProgram]:
    """Lower + compile the three mesh serve entry points at each dp in
    ``dps`` (one whole lane per device — shardcheck is about bytes over
    the interconnect, not batch-shape coverage, which the jaxpr contracts
    already sweep). Inputs are staged exactly as the engine dispatches:
    group axis under ``NamedSharding(P("dp"))``, weights replicated via
    ``serve.meshing.replicate_pipeline``, schedule tables and the
    guidance scalar mesh-replicated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..engine.sampler import encode_prompts, phase2_controller, stage_host
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.mesh import make_mesh
    from ..parallel.sweep import (_stage_replicated, _stage_sharded,
                                  _sweep_jit, _sweep_phase1_jit,
                                  _sweep_phase2_jit)
    from ..serve.meshing import replicate_pipeline
    from ..utils.cache import enable_persistent_cache
    from .contracts import (GATE, PROMPTS, STEPS, _edit_controller,
                            _scan_inputs, _zero_carry, tiny_pipeline)

    enable_persistent_cache()   # the compile step is real XLA work
    if pipe is None:
        pipe = tiny_pipeline()
    ctrl = _edit_controller(pipe)
    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(ctrl)
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    ctx, lats, _ = _scan_inputs(pipe)
    cond = encode_prompts(pipe, list(PROMPTS))
    carry = _zero_carry(pipe, ctrl)
    p2 = phase2_controller(ctrl)

    out: List[MeshProgram] = []
    for dp in mesh_dps(dps):
        mesh = make_mesh(dp, tp=1)
        mpipe = replicate_pipeline(pipe, mesh)
        sch = _stage_replicated(schedule, mesh)
        gs = stage_host(np.float32(7.5), mesh=mesh)
        gspec = NamedSharding(mesh, P("dp"))
        g = dp   # one whole lane bucket per device

        def stage(x):
            return _stage_sharded(
                jnp.broadcast_to(x[None], (g,) + x.shape), gspec)

        ctx_g, lat_g = stage(ctx), stage(lats)
        ctrl_g = jax.tree_util.tree_map(stage, ctrl)
        lowered = {
            f"serve/mesh-dp{dp}": _sweep_jit.lower(
                mpipe.unet_params, mpipe.vae_params, cfg, layout, sch,
                "ddim", ctx_g, lat_g, ctrl_g, gs, None, progress=False,
                gate=GATE, metrics=False),
            f"serve/phase1-mesh-dp{dp}": _sweep_phase1_jit.lower(
                mpipe.unet_params, cfg, layout, sch, "ddim", ctx_g, lat_g,
                ctrl_g, gs, progress=False, gate=GATE, metrics=False),
            f"serve/phase2-mesh-dp{dp}": _sweep_phase2_jit.lower(
                mpipe.unet_params, mpipe.vae_params, cfg, layout, sch,
                "ddim", stage(cond),
                jax.tree_util.tree_map(stage, carry),
                jax.tree_util.tree_map(stage, p2), gs, progress=False,
                gate=GATE, metrics=False),
        }
        for name, low in lowered.items():
            out.append(MeshProgram(
                name=name, dp=dp, lanes=g, stablehlo=low.as_text(),
                hlo=low.compile().as_text(), steps=STEPS))
    return out


def check_collectives(pipe=None, dps: Tuple[int, ...] = SHARDCHECK_DPS,
                      programs: Optional[List[MeshProgram]] = None,
                      declared: Optional[Dict[str, Dict[str, int]]] = None,
                      ) -> Tuple[List[ContractResult], Dict[str, dict]]:
    """Run shardcheck: ``(results, comms table)``. ``programs`` and
    ``declared`` are injection points for the seeded verdict-flip tests
    (tests/test_shardcheck.py); production callers pass neither."""
    if declared is None:
        declared = DECLARED_COLLECTIVES
    if programs is None:
        programs = lower_mesh_programs(pipe, dps=dps)

    results: List[ContractResult] = []
    table: Dict[str, dict] = {}
    for prog in programs:
        ops = shlo_walk.collective_ops(prog.hlo)
        sig = shlo_walk.collective_signature(ops)
        table[prog.name] = {"dp": prog.dp, "lanes": prog.lanes,
                            "steps": prog.steps, **sig}

        # -- collectives-as-declared, both directions -------------------
        want = declared.get(prog.name)
        if want is None:
            results.append(ContractResult(
                "collectives-as-declared", prog.name, False,
                "no DECLARED_COLLECTIVES entry for this program — declare "
                "its collective multiset (empty means collective-free)"))
        else:
            got = sig["ops"]
            undeclared = {k: n - want.get(k, 0) for k, n in got.items()
                          if n > want.get(k, 0)}
            stale = {k: n - got.get(k, 0) for k, n in want.items()
                     if n > got.get(k, 0)}
            if undeclared:
                first = next(op for op in ops if op.kind in undeclared)
                results.append(ContractResult(
                    "collectives-as-declared", prog.name, False,
                    f"undeclared collective(s) {undeclared}: first is "
                    f"{first.describe()}"))
            elif stale:
                results.append(ContractResult(
                    "collectives-as-declared", prog.name, False,
                    f"stale declaration: declared {stale} absent from the "
                    "compiled program (update DECLARED_COLLECTIVES)"))
            else:
                results.append(ContractResult(
                    "collectives-as-declared", prog.name, True,
                    f"ops {got or '{}'} = declared, "
                    f"{sig['bytes_per_step']}B/step + "
                    f"{sig['bytes_once']}B once"))

        # -- no-hidden-resharding ---------------------------------------
        changes = shlo_walk.sharding_changes(prog.stablehlo)
        if changes:
            worst = next((c for c in changes if c.forces_replication),
                         changes[0])
            results.append(ContractResult(
                "no-hidden-resharding", prog.name, False,
                f"{len(changes)} sharding-changing op(s): "
                f"{worst.describe()}"
                + (" — full replication of a sharded tensor"
                   if worst.forces_replication else "")))
        else:
            results.append(ContractResult(
                "no-hidden-resharding", prog.name, True,
                "no sharding-changing ops"))

        # -- no-host-boundary -------------------------------------------
        host = (shlo_walk.host_boundary_ops(prog.stablehlo)
                + shlo_walk.host_boundary_ops(prog.hlo))
        results.append(ContractResult(
            "no-host-boundary", prog.name, not host,
            (f"host-boundary op(s) in a mesh program: {sorted(set(host))}"
             if host else "no host callbacks")))

    # -- stale program-level declarations -------------------------------
    swept = {p.name for p in programs}
    reachable = {t.format(dp=d) for d in SHARDCHECK_DPS
                 for t in _NAME_TEMPLATES}
    for name in sorted(declared):
        if name in swept:
            continue
        if name in reachable and name not in {
                t.format(dp=d) for d in mesh_dps(dps)
                for t in _NAME_TEMPLATES}:
            continue   # environment-limited (not enough devices): not stale
        results.append(ContractResult(
            "collectives-as-declared", name, False,
            "stale declaration: no canonical mesh program by this name "
            "was swept (remove or rename the DECLARED_COLLECTIVES entry)"))
    return results, table
