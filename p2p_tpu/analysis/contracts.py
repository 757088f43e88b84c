"""Pass 2 — traced-program contracts.

Trace the canonical programs of the stack on a tiny pipeline (abstract
tracing only — ``jax.make_jaxpr``, no XLA compile) and assert jaxpr-level
contracts that hand-written review keeps re-checking:

- ``no-f64`` — no ``convert_element_type`` to float64 and no f64-dtyped
  value anywhere in any canonical program. Under the default x64-off
  config this can only fire on an explicit promotion; it is the tripwire
  for the day someone enables x64 "just for one test".
- ``hot-scan-callbacks`` — the phase-2 scan and the serve batch programs
  carry **zero** host callbacks when telemetry is off (the disabled-mode
  program-identity discipline), and with telemetry on, the only callback
  primitive in a hot scan is ``debug_callback`` — the registered obs-sink
  channel (``utils.progress``). ``io_callback``/``pure_callback`` in a hot
  scan would serialize the device against the host every step.
- ``phase2-footprint`` — the phase-2 scan body carries no CFG-doubled
  ``2B``-batch tensors (the ISSUE 1 jaxpr proof from
  ``tests/test_phase_cache.py``, generalized to every gated surface
  including the vmapped serve programs) and is strictly smaller than the
  phase-1 body.
- ``donation-as-declared`` — each canonical jitted entry point's buffer
  donation matches :data:`DECLARED_DONATION`. Today every program declares
  *no* donation (``_sweep_jit`` spells ``donate_argnums=()`` explicitly —
  sweep inputs are caller-reused); a future PR that donates must update
  the declaration, and one that declares without the lowering actually
  aliasing (or vice versa) fails here.
- ``trace-invisible`` — re-tracing every canonical program under a *live*
  request-scoped flight tracer (``obs.flight``: open context, attached
  spans) yields byte-identical jaxpr fingerprints: flipping flight
  tracing on/off can never change a compiled program.
- ``no-materialized-probs`` — a canonical program dispatched through the
  fused-edit kernel config (:func:`kernel_programs`) carries no
  CFG-doubled ``(2B, heads, P, K)`` attention-probability softmax
  anywhere: the prompt-to-prompt edit runs inside the attention tile, so
  the probability tensor never exists as a program-level value. Each
  fused program is paired with its ``kernels=None`` twin, which must trip
  the detector (non-vacuity witness).

Programs traced (:func:`canonical_programs`): text2image ungated + gated
(phase 1/2), serve batch programs across every lane bucket (1/2/4/8, the
``BUCKET_SIZES`` padding contract), the disaggregated phase-1/phase-2
POOL programs at the same buckets (phase-disaggregated continuous
batching — ``phase2-footprint`` pairs each phase-2 pool program with its
phase-1 twin, since each pool compiles a single scan), the SHARDED serve
programs (mesh-parallel serving: the same three serve tracers with their
group-axis inputs placed under a ``NamedSharding(P("dp"))`` on a live
``dp`` mesh — ``dp=2`` when the process has the devices, degrading to a
one-device mesh otherwise, so the sweep always runs; the behavioral mesh
legs live in tests/test_serve_mesh.py and the ``mesh_parity`` quality
gate), and the two inversion programs. The tiny pipeline is the same
construction the golden tests use (random weights; contracts are
shape/structure properties, weights never matter).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from . import jaxpr_walk

#: Steps/gate the canonical programs trace with — small (tracing cost is
#: linear in scan length only at the python level; the jaxpr scan body is
#: length-independent) but ≥ 3 so gate=2 leaves both phases non-trivial.
STEPS = 3
GATE = 2
PROMPTS = ("a squirrel eating a burger", "a squirrel eating a lasagna")

#: program name -> donated argument indices the code *declares*. The
#: contract checks the lowering agrees in both directions, over every
#: jitted entry point the serve stack dispatches: the monolithic sweep,
#: the disaggregated phase-1/phase-2 pool programs, and all three again
#: as MESH programs (dp-sharded group inputs — donation lowers through
#: the partitioner, so the mesh twins are checked in their own right).
#: Today every program declares *no* donation (sweep inputs are
#: caller-reused; a hand-off carry outlives its phase-2 dispatch via the
#: journal spill path).
DECLARED_DONATION: Dict[str, Tuple[int, ...]] = {
    "text2image": (),
    "sweep": (),
    "sweep/phase1": (),
    "sweep/phase2": (),
    "sweep/mesh": (),
    "sweep/phase1-mesh": (),
    "sweep/phase2-mesh": (),
}


@dataclasses.dataclass
class ContractResult:
    contract: str
    program: str
    ok: bool
    detail: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def format(self) -> str:
        return (f"{'ok  ' if self.ok else 'FAIL'} {self.contract:22s} "
                f"{self.program:18s} {self.detail}")


def tiny_pipeline():
    """The TINY random-weight pipeline (the golden tests' construction,
    package-local so the analyzer has no test dependency)."""
    import jax

    from ..engine.sampler import Pipeline
    from ..models import TINY, init_text_encoder, init_unet
    from ..models import vae as vae_mod
    from ..utils.tokenizer import HashWordTokenizer

    tok = HashWordTokenizer(vocab_size=TINY.text.vocab_size,
                            model_max_length=TINY.text.max_length)
    return Pipeline(
        config=TINY,
        unet_params=init_unet(jax.random.PRNGKey(0), TINY.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), TINY.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), TINY.vae),
        tokenizer=tok,
    )


@dataclasses.dataclass
class Program:
    """One traced canonical program plus the metadata contracts key on."""

    name: str
    jaxpr: object                 # ClosedJaxpr
    group_batch: int              # B (prompts per edit group)
    gate: Optional[int]           # phase-2 start, None = ungated
    metrics: bool                 # telemetry traced in?
    lead_dims: Tuple[int, ...] = ()   # vmap prefix (G,) for serve programs
    max_tokens: Optional[int] = None  # token-major detector bound


def _edit_controller(pipe):
    from ..cli import controller_from_opts
    from ..models.config import unet_layout

    # the programs below are traced past ``text2image``/``sweep``, so the
    # defaults are taken against the model here, as those entrances do
    return unet_layout(pipe.config.unet).resolve(controller_from_opts(
        list(PROMPTS), pipe.tokenizer, STEPS, mode="replace",
        cross_steps=0.8, self_steps=0.4))


def _scan_inputs(pipe):
    import jax.numpy as jnp

    from ..engine.sampler import encode_prompts

    b = len(PROMPTS)
    cond = encode_prompts(pipe, list(PROMPTS))
    uncond = encode_prompts(pipe, [""] * b)
    ctx = jnp.concatenate([uncond, cond], axis=0)
    lats = jnp.zeros((b,) + pipe.latent_shape)
    return ctx, lats, jnp.float32(7.5)


def _trace_denoise(pipe, ctrl, gate, metrics, kernels=None):
    import jax

    from ..engine.sampler import _denoise_scan
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod

    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(ctrl)
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    ctx, lats, gs = _scan_inputs(pipe)

    def run(up, ctx, lats, gs):
        return _denoise_scan(up, cfg, layout, schedule, "ddim", ctx, lats,
                             ctrl, gs, gate=gate, metrics=metrics,
                             kernels=kernels)

    return jax.make_jaxpr(run)(pipe.unet_params, ctx, lats, gs)


def _mesh_dp() -> int:
    """The dp width the sharded canonical programs trace at: 2 when the
    process has at least two devices, else a one-device mesh — the sweep
    must run everywhere the analyzer does (a bare ``p2p-tpu check
    --static`` sees one CPU device; the test/gate environments force a
    virtual 8-device platform)."""
    import jax

    return 2 if len(jax.devices()) >= 2 else 1


def _stage_dp(x, mesh):
    """Place a group-axis value under the serve mesh's data sharding —
    exactly what the engine's dispatch staging does."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(mesh, P("dp")))


def _trace_sweep(pipe, ctrl, bucket, gate, metrics, mesh=None, reuse=None,
                 kernels=None):
    import jax
    import jax.numpy as jnp

    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.sweep import _sweep_jit

    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(ctrl)
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    ctx, lats, gs = _scan_inputs(pipe)
    ctx_g = jnp.broadcast_to(ctx[None], (bucket,) + ctx.shape)
    lat_g = jnp.broadcast_to(lats[None], (bucket,) + lats.shape)
    ctrl_g = (None if ctrl is None else jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (bucket,) + x.shape), ctrl))
    if mesh is not None:
        ctx_g, lat_g = _stage_dp(ctx_g, mesh), _stage_dp(lat_g, mesh)
        ctrl_g = (None if ctrl_g is None else jax.tree_util.tree_map(
            lambda x: _stage_dp(x, mesh), ctrl_g))

    def run(up, vp, ctx_g, lat_g, ctrl_g, gs):
        return _sweep_jit(up, vp, cfg, layout, schedule, "ddim", ctx_g,
                          lat_g, ctrl_g, gs, None, progress=False,
                          gate=gate, metrics=metrics, reuse=reuse,
                          kernels=kernels)

    return jax.make_jaxpr(run)(pipe.unet_params, pipe.vae_params, ctx_g,
                               lat_g, ctrl_g, gs)


def _zero_carry(pipe, ctrl, reuse=None):
    """A zero-valued per-group PhaseCarry with the shapes the phase-1 pool
    program produces for this controller — the phase-2 pool trace input.
    ``reuse`` (a resolved reuse schedule, ISSUE 15) swaps the all-cross
    AttnCache for the schedule's ever-cached leaf set."""
    import jax.numpy as jnp

    from ..controllers.base import init_store_state
    from ..engine.sampler import PhaseCarry
    from ..models.config import unet_layout
    from ..models.unet import init_attn_cache
    from ..ops import schedulers as sched_mod

    layout = unet_layout(pipe.config.unet).for_readers(ctrl)
    b = len(PROMPTS)
    lat = jnp.zeros((b,) + pipe.latent_shape)
    state = init_store_state(layout, b)
    if reuse is not None:
        from ..engine import reuse as reuse_mod

        cache = reuse_mod.init_schedule_cache(layout, reuse, b, phase=2,
                                              dtype=lat.dtype)
    else:
        cache = init_attn_cache(layout, b, dtype=lat.dtype)
    return PhaseCarry(
        latents=lat, resid=jnp.zeros_like(lat),
        cache=cache,
        ms=sched_mod.init_multistep_state("ddim", lat.shape, lat.dtype),
        state=state)


def _trace_sweep_phase1(pipe, ctrl, bucket, gate, metrics, mesh=None,
                        reuse=None):
    import jax
    import jax.numpy as jnp

    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.sweep import _sweep_phase1_jit

    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(ctrl)
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    ctx, lats, gs = _scan_inputs(pipe)
    ctx_g = jnp.broadcast_to(ctx[None], (bucket,) + ctx.shape)
    lat_g = jnp.broadcast_to(lats[None], (bucket,) + lats.shape)
    ctrl_g = (None if ctrl is None else jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (bucket,) + x.shape), ctrl))
    if mesh is not None:
        ctx_g, lat_g = _stage_dp(ctx_g, mesh), _stage_dp(lat_g, mesh)
        ctrl_g = (None if ctrl_g is None else jax.tree_util.tree_map(
            lambda x: _stage_dp(x, mesh), ctrl_g))

    def run(up, ctx_g, lat_g, ctrl_g, gs):
        return _sweep_phase1_jit(up, cfg, layout, schedule, "ddim", ctx_g,
                                 lat_g, ctrl_g, gs, progress=False,
                                 gate=gate, metrics=metrics, reuse=reuse)

    return jax.make_jaxpr(run)(pipe.unet_params, ctx_g, lat_g, ctrl_g, gs)


def _trace_sweep_phase2(pipe, ctrl, bucket, gate, metrics, mesh=None,
                        reuse=None):
    import jax
    import jax.numpy as jnp

    from ..engine.sampler import encode_prompts, phase2_controller
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.sweep import _sweep_phase2_jit

    cfg = pipe.config
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    cond = encode_prompts(pipe, list(PROMPTS))
    carry = _zero_carry(pipe, ctrl, reuse=reuse)
    p2 = phase2_controller(ctrl)
    layout = unet_layout(cfg.unet).for_readers(p2)

    def lead(x):
        return jnp.broadcast_to(x[None], (bucket,) + x.shape)

    ctx_g = lead(cond)
    carry_g = jax.tree_util.tree_map(lead, carry)
    ctrl_g = None if p2 is None else jax.tree_util.tree_map(lead, p2)
    if mesh is not None:
        ctx_g = _stage_dp(ctx_g, mesh)
        carry_g = jax.tree_util.tree_map(lambda x: _stage_dp(x, mesh),
                                         carry_g)
        ctrl_g = (None if ctrl_g is None else jax.tree_util.tree_map(
            lambda x: _stage_dp(x, mesh), ctrl_g))
    gs = jnp.float32(7.5)

    def run(up, vp, ctx_g, carry_g, ctrl_g, gs):
        return _sweep_phase2_jit(up, vp, cfg, layout, schedule, "ddim",
                                 ctx_g, carry_g, ctrl_g, gs, progress=False,
                                 gate=gate, metrics=metrics, reuse=reuse)

    return jax.make_jaxpr(run)(pipe.unet_params, pipe.vae_params, ctx_g,
                               carry_g, ctrl_g, gs)


def _trace_invert(pipe, metrics):
    """The two inversion programs: DDIM forward-invert and the null-text
    optimizer outer scan."""
    import jax
    import jax.numpy as jnp

    from ..engine.inversion import _ddim_invert_jit, _null_optimize_jit
    from ..ops import schedulers as sched_mod

    cfg = pipe.config
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    img = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
    cond = jnp.zeros((1, cfg.unet.context_len, cfg.unet.context_dim))
    uncond = jnp.zeros_like(cond)

    def run_inv(up, vp, img, cond):
        return _ddim_invert_jit(up, vp, cfg, schedule, img, cond,
                                progress=False, sp=None, metrics=metrics)

    inv = jax.make_jaxpr(run_inv)(pipe.unet_params, pipe.vae_params, img,
                                  cond)

    lat_shape = (STEPS + 1, 1) + pipe.latent_shape
    lats = jnp.zeros(lat_shape)

    def run_null(up, lats, cond, uncond):
        return _null_optimize_jit(up, cfg, schedule, lats, uncond, cond,
                                  jnp.float32(7.5), 2, jnp.float32(1e-5),
                                  progress=False, sp=None, metrics=metrics)

    null = jax.make_jaxpr(run_null)(pipe.unet_params, lats, cond, uncond)
    return inv, null


def canonical_programs(pipe=None, buckets=(1, 2, 4, 8),
                       metrics=False) -> List[Program]:
    """Trace every canonical program of the stack. ``metrics`` traces the
    telemetry variant (used by the hot-scan-callback contract's
    only-debug-callback half)."""
    if pipe is None:
        pipe = tiny_pipeline()
    b = len(PROMPTS)
    ctrl = _edit_controller(pipe)
    programs = [
        Program("text2image/ungated",
                _trace_denoise(pipe, ctrl, gate=None, metrics=metrics),
                group_batch=b, gate=None, metrics=metrics),
        Program("text2image/gated",
                _trace_denoise(pipe, ctrl, gate=GATE, metrics=metrics),
                group_batch=b, gate=GATE, metrics=metrics),
    ]
    for g in buckets:
        programs.append(Program(
            f"serve/bucket{g}",
            _trace_sweep(pipe, ctrl, bucket=g, gate=GATE, metrics=metrics),
            group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
    for g in buckets:
        # The disaggregated pool programs (phase-disaggregated continuous
        # batching): phase 1 and phase 2 compile separately; the
        # phase2-footprint contract pairs them by bucket.
        programs.append(Program(
            f"serve/phase1-bucket{g}",
            _trace_sweep_phase1(pipe, ctrl, bucket=g, gate=GATE,
                                metrics=metrics),
            group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
        programs.append(Program(
            f"serve/phase2-bucket{g}",
            _trace_sweep_phase2(pipe, ctrl, bucket=g, gate=GATE,
                                metrics=metrics),
            group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
    # Sharded serve programs (mesh-parallel serving): the same three serve
    # tracers with group-axis inputs placed under NamedSharding(P("dp")) on
    # a live dp mesh — the engine's `--mesh` dispatch shape. One bucket of
    # dp whole per-device lanes keeps the sweep cheap; the footprint pair
    # uses the same phase1-/phase2- naming so it pairs like the rest.
    from ..parallel.mesh import make_mesh

    dp = _mesh_dp()
    mesh = make_mesh(dp, tp=1)
    g = dp * 2  # two lanes per device: the doubled-batch detector stays
    #             non-vacuous and the per-device sub-batch is a real batch
    programs.append(Program(
        f"serve/mesh-dp{dp}x{g}",
        _trace_sweep(pipe, ctrl, bucket=g, gate=GATE, metrics=metrics,
                     mesh=mesh),
        group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
    programs.append(Program(
        f"serve/phase1-mesh-dp{dp}x{g}",
        _trace_sweep_phase1(pipe, ctrl, bucket=g, gate=GATE,
                            metrics=metrics, mesh=mesh),
        group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
    programs.append(Program(
        f"serve/phase2-mesh-dp{dp}x{g}",
        _trace_sweep_phase2(pipe, ctrl, bucket=g, gate=GATE,
                            metrics=metrics, mesh=mesh),
        group_batch=b, gate=GATE, metrics=metrics, lead_dims=(g,)))
    inv, null = _trace_invert(pipe, metrics=metrics)
    programs.append(Program("invert/ddim", inv, group_batch=1, gate=None,
                            metrics=metrics))
    programs.append(Program("invert/null_text", null, group_batch=1,
                            gate=None, metrics=metrics))
    return programs


def scheduled_programs(pipe=None, spec=None, buckets=(1,),
                       metrics=False) -> List[Program]:
    """Scheduled canonical programs (ISSUE 15): the committed default
    reuse-schedule artifact (or ``spec``) resolved at the canonical
    STEPS, traced as the monolithic serve program and the two pool
    programs — the quality gate's ``schedule`` leg runs the no-f64 and
    hot-scan-callback contracts over these, so a schedule that sneaks a
    host callback or an f64 promotion into a segment fails CI exactly
    like a canonical program would. The spec is resolved with a
    NON-uniform fallback: if the artifact happens to normalize to the
    uniform gate at this scan length, the trace would silently collapse
    onto already-covered programs, so that case raises instead."""
    import jax

    from ..engine import reuse as reuse_mod
    from ..models.config import unet_layout

    if pipe is None:
        pipe = tiny_pipeline()
    if spec is None:
        import json
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "tools", "schedules", "default_v1.json")
        with open(path) as f:
            spec = json.load(f)
    b = len(PROMPTS)
    ctrl = _edit_controller(pipe)
    layout = unet_layout(pipe.config.unet)
    sched = reuse_mod.resolve_schedule(spec, layout, STEPS, ctrl)
    if sched.uniform_gate is not None:
        raise ValueError(
            f"schedule spec resolves to the uniform gate at {STEPS} scan "
            "steps — the scheduled contract sweep would trace nothing new")
    gate = sched.cfg_gate
    programs = []
    import warnings

    with warnings.catch_warnings():
        # Window-conflict warnings are the workload's business (the tiny
        # contract controller has a long edit window on purpose); the
        # contract sweep only cares about program structure.
        warnings.simplefilter("ignore")
        for g in buckets:
            programs.append(Program(
                f"serve/sched-bucket{g}",
                _trace_sweep(pipe, ctrl, bucket=g, gate=gate,
                             metrics=metrics, reuse=sched),
                group_batch=b, gate=gate, metrics=metrics, lead_dims=(g,)))
            programs.append(Program(
                f"serve/sched-phase1-bucket{g}",
                _trace_sweep_phase1(pipe, ctrl, bucket=g, gate=gate,
                                    metrics=metrics,
                                    reuse=reuse_mod.phase1_view(sched)),
                group_batch=b, gate=gate, metrics=metrics, lead_dims=(g,)))
            programs.append(Program(
                f"serve/sched-phase2-bucket{g}",
                _trace_sweep_phase2(pipe, ctrl, bucket=g, gate=gate,
                                    metrics=metrics,
                                    reuse=reuse_mod.phase2_view(sched)),
                group_batch=b, gate=gate, metrics=metrics, lead_dims=(g,)))
    return programs


def _kernel_controller(pipe):
    """The kernel-twin controller: a replace edit whose window covers every
    TINY attention site (``self_max_pixels`` at the largest level) with
    ``store=False`` — every controller-touched site is kernel-compilable
    and the fused twin has ZERO materialized CFG-doubled probability
    tensors by construction. A site stored for a reader stays
    materialized by design (``kernel_edit_spec`` declines it); these
    programs have no reader (``AttnLayout.for_readers``), and ``store=False``
    says so whatever the tracing helper does with the layout."""
    from ..controllers import factory

    size = pipe.config.unet.sample_size
    return factory.attention_replace(
        list(PROMPTS), STEPS, cross_replace_steps=0.8,
        self_replace_steps=0.4, tokenizer=pipe.tokenizer,
        self_max_pixels=size * size, max_len=pipe.config.text.max_length,
        store=False)


def kernel_programs(pipe=None, metrics=False) -> List[Program]:
    """Kernel-bearing canonical program twins (fused-edit Pallas dispatch)
    plus their materialized counterparts under the SAME controller: the
    sequential sampler ungated + gated, and the monolithic serve program at
    one bucket. Each ``<name>-fused`` program traces with
    ``KernelConfig(interpret=True)`` (the CPU-traceable rehearsal config —
    the pallas_call program structure is identical to the compiled-TPU
    one); ``<name>`` traces the exact same program with ``kernels=None``,
    giving :func:`check_no_materialized_probs` its non-vacuity witness."""
    from ..kernels import KernelConfig

    if pipe is None:
        pipe = tiny_pipeline()
    b = len(PROMPTS)
    ctrl = _kernel_controller(pipe)
    kc = KernelConfig(interpret=True)
    programs = []
    for label, gate in (("ungated", None), ("gated", GATE)):
        programs.append(Program(
            f"kernel/{label}",
            _trace_denoise(pipe, ctrl, gate=gate, metrics=metrics),
            group_batch=b, gate=gate, metrics=metrics))
        programs.append(Program(
            f"kernel/{label}-fused",
            _trace_denoise(pipe, ctrl, gate=gate, metrics=metrics,
                           kernels=kc),
            group_batch=b, gate=gate, metrics=metrics))
    programs.append(Program(
        "kernel/serve-bucket1",
        _trace_sweep(pipe, ctrl, bucket=1, gate=GATE, metrics=metrics),
        group_batch=b, gate=GATE, metrics=metrics, lead_dims=(1,)))
    programs.append(Program(
        "kernel/serve-bucket1-fused",
        _trace_sweep(pipe, ctrl, bucket=1, gate=GATE, metrics=metrics,
                     kernels=kc),
        group_batch=b, gate=GATE, metrics=metrics, lead_dims=(1,)))
    return programs


# ---------------------------------------------------------------------------
# Contracts
# ---------------------------------------------------------------------------


def check_no_f64(programs: List[Program]) -> List[ContractResult]:
    out = []
    for p in programs:
        bad = jaxpr_walk.f64_eqns(jaxpr_walk.all_eqns(p.jaxpr))
        detail = (f"{len(bad)} f64 eqn(s), first: "
                  f"{bad[0].primitive.name}" if bad else "no f64 values")
        out.append(ContractResult("no-f64", p.name, not bad, detail))
    return out


def _hot_scans(p: Program) -> List[Tuple[str, list]]:
    """(label, body eqns) of the hot scans a program carries: for a gated
    program, the phase-2 scan (last top-level scan); serve programs are hot
    end to end, so every scan counts."""
    scans = jaxpr_walk.top_level_scans(p.jaxpr)
    if not scans:
        return []
    if p.name.startswith("serve/"):
        return [(f"scan{i}", jaxpr_walk.scan_body(s))
                for i, s in enumerate(scans)]
    if p.gate is not None:
        return [("phase2", jaxpr_walk.scan_body(scans[-1]))]
    return []


def check_hot_scan_callbacks(programs: List[Program]) -> List[ContractResult]:
    out = []
    for p in programs:
        for label, body in _hot_scans(p):
            cbs = jaxpr_walk.callback_eqns(body)
            if not p.metrics:
                ok = not cbs
                detail = (f"{label}: {len(cbs)} callback(s) with telemetry "
                          f"off" if cbs else f"{label}: no callbacks")
            else:
                alien = [e for e in cbs
                         if e.primitive.name != "debug_callback"]
                ok = not alien
                detail = (f"{label}: non-obs callback(s) "
                          f"{sorted({e.primitive.name for e in alien})}"
                          if alien else
                          f"{label}: {len(cbs)} debug_callback(s) only")
            out.append(ContractResult("hot-scan-callbacks", p.name, ok,
                                      detail))
    return out


def _doubled_detector(p: Program):
    """The CFG-doubled-batch detector for one program: plain ``(2B, ...)``
    shapes for unbatched programs; explicit ``(G, 2B, ...)`` prefixes plus
    vmap-folded ``(G·2B, h, w, c)`` conv activations for vmapped serve
    programs. Only these exact forms count: an unqualified leading-dim
    match would collide with G·B phase-2 activations whenever G·B == 2B
    (bucket 2 at B=2)."""

    def doubled(body):
        shapes = jaxpr_walk.eqn_shapes(body)
        if not p.lead_dims:
            return jaxpr_walk.doubled_batch_shapes(shapes, p.group_batch)
        g = p.lead_dims[0]
        return (jaxpr_walk.doubled_batch_shapes(
                    shapes, p.group_batch, lead_dims=p.lead_dims)
                + jaxpr_walk.folded_batch_shapes(
                    shapes, g * 2 * p.group_batch))

    return doubled


def check_pool_footprint(programs: List[Program]) -> List[ContractResult]:
    """phase2-footprint for the DISAGGREGATED pool programs: each pool
    compiles one scan, so the two-phase comparison pairs
    ``serve/phase1-bucketG`` with ``serve/phase2-bucketG`` — the phase-2
    pool program must carry no CFG-doubled tensors anywhere in its scan
    and its scan body must be strictly smaller than its phase-1 twin's."""
    out = []
    pool = {p.name: p for p in programs
            if p.name.startswith("serve/phase")}
    p1_names = sorted(n for n in pool if n.startswith("serve/phase1-"))
    for n1 in p1_names:
        n2 = n1.replace("phase1-", "phase2-")
        pair_name = n2
        if n2 not in pool:
            out.append(ContractResult(
                "phase2-footprint", pair_name, False,
                f"phase-1 pool program {n1} has no phase-2 twin"))
            continue
        p1, p2 = pool[n1], pool[n2]
        s1 = jaxpr_walk.top_level_scans(p1.jaxpr)
        s2 = jaxpr_walk.top_level_scans(p2.jaxpr)
        if len(s1) != 1 or len(s2) != 1:
            out.append(ContractResult(
                "phase2-footprint", pair_name, False,
                f"pool programs must carry exactly one scan each, found "
                f"{len(s1)}/{len(s2)}"))
            continue
        body1 = jaxpr_walk.scan_body(s1[0])
        body2 = jaxpr_walk.scan_body(s2[0])
        d1 = _doubled_detector(p1)(body1)
        d2 = _doubled_detector(p2)(body2)
        if not d1:
            out.append(ContractResult(
                "phase2-footprint", pair_name, False,
                "detector vacuous: the phase-1 pool scan carries no "
                "CFG-doubled batch"))
            continue
        ok = not d2 and len(body2) < len(body1)
        detail = (f"pool scan {len(body2)} eqns < phase1 {len(body1)}, "
                  f"no 2B tensors" if ok else
                  (f"phase-2 pool scan still carries 2B tensors: "
                   f"{sorted(set(d2))[:4]}" if d2 else
                   f"phase-2 pool scan ({len(body2)} eqns) not smaller "
                   f"than phase-1 ({len(body1)})"))
        out.append(ContractResult("phase2-footprint", pair_name, ok, detail))
    return out


def check_phase2_footprint(programs: List[Program]) -> List[ContractResult]:
    """The generalized ISSUE 1 proof: phase 2 carries no CFG-doubled batch
    and is strictly smaller than phase 1 — on every gated surface. The
    single-program (two-scan) surfaces are checked here; the disaggregated
    pool programs pair up in :func:`check_pool_footprint`."""
    out = []
    for p in programs:
        if p.gate is None or p.name.startswith("invert/") \
                or p.name.startswith("serve/phase"):
            continue
        scans = jaxpr_walk.top_level_scans(p.jaxpr)
        if len(scans) != 2:
            out.append(ContractResult(
                "phase2-footprint", p.name, False,
                f"expected a two-phase scan, found {len(scans)} top-level "
                "scan(s)"))
            continue
        body1 = jaxpr_walk.scan_body(scans[0])
        body2 = jaxpr_walk.scan_body(scans[1])
        doubled = _doubled_detector(p)
        d1, d2 = doubled(body1), doubled(body2)
        if not d1:
            out.append(ContractResult(
                "phase2-footprint", p.name, False,
                "detector vacuous: phase 1 carries no CFG-doubled batch"))
            continue
        ok = not d2 and len(body2) < len(body1)
        detail = (f"phase2 {len(body2)} eqns < phase1 {len(body1)}, "
                  f"no 2B tensors" if ok else
                  (f"phase2 still carries 2B tensors: "
                   f"{sorted(set(d2))[:4]}" if d2 else
                   f"phase2 body ({len(body2)} eqns) not smaller than "
                   f"phase1 ({len(body1)})"))
        out.append(ContractResult("phase2-footprint", p.name, ok, detail))
    return out


def _materialized_probs_eqns(p: Program) -> List[Tuple[int, ...]]:
    """Shapes of CFG-doubled attention-probability softmaxes a program
    materializes: ``exp`` equations over 4-D f32 operands (plus the vmap
    group prefix for serve programs) whose CFG batch dim is exactly ``2B``.
    In this stack the only 4-D f32 exp with a CFG-doubled leading dim is
    the attention softmax (``models.nn.attention_probs``); the fused-edit
    kernel's in-tile softmax runs on 2-D ``(block_q, K)`` tiles, so
    recursing into pallas_call bodies cannot false-positive, and the
    phase-2 single-branch path (batch ``B``) is out of scope by
    construction — the contract is about the ``(2B, heads, P, K)`` tensor
    the ISSUE's roofline names."""
    lead = len(p.lead_dims)
    hits = []
    for eqn in jaxpr_walk.all_eqns(p.jaxpr):
        if eqn.primitive.name != "exp":
            continue
        aval = eqn.invars[0].aval
        shape = tuple(getattr(aval, "shape", ()))
        if (len(shape) == 4 + lead and str(getattr(aval, "dtype", ""))
                == "float32" and shape[lead] == 2 * p.group_batch):
            hits.append(shape)
    return hits


def check_no_materialized_probs(
        programs: List[Program]) -> List[ContractResult]:
    """The kernel-bearing twin contract (ISSUE 16): a canonical program
    dispatched through the fused-edit kernel config materializes NO
    CFG-doubled ``(2B, heads, P, K)`` attention-probability tensor — the
    edit runs inside the attention tile, so the probs never exist as a
    program-level value (and therefore never reach HBM on chip). Each
    ``<name>-fused`` program is paired with its ``<name>`` materialized
    twin (same controller, ``kernels=None``), which must trip the detector
    — a vacuous detector (e.g. the probs shape drifting past the pattern)
    fails rather than silently passing."""
    out = []
    by_name = {p.name: p for p in programs}
    for name in sorted(by_name):
        if not name.endswith("-fused"):
            continue
        p = by_name[name]
        twin = by_name.get(name[:-len("-fused")])
        if twin is None:
            out.append(ContractResult(
                "no-materialized-probs", name, False,
                "fused program has no materialized twin in the sweep"))
            continue
        witness = _materialized_probs_eqns(twin)
        if not witness:
            out.append(ContractResult(
                "no-materialized-probs", name, False,
                f"detector vacuous: materialized twin {twin.name} shows no "
                "CFG-doubled softmax"))
            continue
        hits = _materialized_probs_eqns(p)
        ok = not hits
        detail = (f"0 materialized 2B-probs (twin shows "
                  f"{len(witness)})" if ok else
                  f"fused program still materializes CFG-doubled probs: "
                  f"{sorted(set(hits))[:4]}")
        out.append(ContractResult("no-materialized-probs", name, ok, detail))
    return out


def check_trace_invisible(pipe=None, buckets=(1,),
                          programs_fn=None) -> List[ContractResult]:
    """The flight-tracing half of the disabled-invisible discipline:
    flipping request-scoped tracing on/off must leave every canonical
    program fingerprint identical — a hard error otherwise.

    Flight tracing (``obs.flight``) is host-side by design; the day
    someone threads a tracer hook into a traced function, the retrace
    under a live tracer (open context, attached spans — the exact
    conditions the serve loop creates around every dispatch) diverges
    from the quiescent fingerprint and this contract names the program.
    ``programs_fn`` is an injection point for the verdict-flip proof in
    tests/test_jaxcheck.py."""
    import hashlib

    from ..obs import flight as flight_mod
    from ..obs import spans as spans_mod

    if pipe is None:
        pipe = tiny_pipeline()
    fn = programs_fn or canonical_programs

    def fingerprints() -> Dict[str, str]:
        return {p.name: hashlib.sha256(str(p.jaxpr).encode()).hexdigest()
                for p in fn(pipe, buckets=buckets, metrics=False)}

    base = fingerprints()
    tracer = flight_mod.FlightTracer()
    tracer.admit("jaxcheck-probe", 0.0, gated=True)
    tracer.segment("jaxcheck-probe", "run", 0.0, 1.0, pool="phase1")
    with spans_mod.attach(traces=tracer.current_trace_id("jaxcheck-probe")):
        live = fingerprints()
    tracer.finish("jaxcheck-probe", "ok", 1.0)
    out = []
    for name in sorted(base):
        if name not in live:
            out.append(ContractResult(
                "trace-invisible", name, False,
                "program missing from the tracer-live sweep"))
            continue
        ok = base[name] == live[name]
        detail = ("fingerprint identical with tracing on/off" if ok else
                  f"fingerprint changed under a live flight tracer: "
                  f"{base[name][:12]} != {live[name][:12]}")
        out.append(ContractResult("trace-invisible", name, ok, detail))
    return out


def _donated_params(lowered_text: str) -> int:
    """Count donated parameters in a lowering's StableHLO text: XLA marks
    them ``jax.buffer_donor`` (or legacy ``tf.aliasing_output``)."""
    return (lowered_text.count("jax.buffer_donor")
            + lowered_text.count("tf.aliasing_output"))


def _donation_lowerings(pipe) -> Dict[str, str]:
    """StableHLO text of every entry point :data:`DECLARED_DONATION`
    names: the two historical programs plus the pool programs and their
    mesh twins (group inputs staged under ``NamedSharding(P("dp"))`` on a
    :func:`_mesh_dp`-wide mesh, the engine's ``--mesh`` dispatch shape)."""
    import jax
    import jax.numpy as jnp

    from ..engine.sampler import (_text2image_jit, encode_prompts,
                                  phase2_controller)
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.mesh import make_mesh
    from ..parallel.sweep import (_sweep_jit, _sweep_phase1_jit,
                                  _sweep_phase2_jit)

    cfg = pipe.config
    schedule = sched_mod.schedule_from_config(STEPS, cfg.scheduler,
                                              kind="ddim")
    ctx, lats, gs = _scan_inputs(pipe)
    b = len(PROMPTS)
    cond, uncond = ctx[b:], ctx[:b]
    ctrl = _edit_controller(pipe)
    layout = unet_layout(cfg.unet).for_readers(ctrl)
    carry = _zero_carry(pipe, ctrl)
    p2 = phase2_controller(ctrl)
    cond_b = encode_prompts(pipe, list(PROMPTS))
    lead1 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[None], t)                  # one-lane group axis
    dp = _mesh_dp()
    mesh = make_mesh(dp, tp=1)

    def lead_dp(t):
        # dp whole lanes, staged under the engine's group-axis sharding
        # (a 1-lane group can't split over a dp>1 mesh).
        return jax.tree_util.tree_map(
            lambda x: _stage_dp(jnp.broadcast_to(x[None], (dp,) + x.shape),
                                mesh), t)

    lowerings = {
        "text2image": _text2image_jit.lower(
            pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
            "ddim", cond, uncond, lats, None, gs, None, False,
            progress=False, sp=None, gate=None, metrics=False),
        "sweep": _sweep_jit.lower(
            pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
            "ddim", ctx[None], lats[None], None, gs, None, progress=False,
            gate=None, metrics=False),
        "sweep/phase1": _sweep_phase1_jit.lower(
            pipe.unet_params, cfg, layout, schedule, "ddim", ctx[None],
            lats[None], lead1(ctrl), gs, progress=False, gate=GATE,
            metrics=False),
        "sweep/phase2": _sweep_phase2_jit.lower(
            pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
            "ddim", cond_b[None], lead1(carry), lead1(p2), gs,
            progress=False, gate=GATE, metrics=False),
        "sweep/mesh": _sweep_jit.lower(
            pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
            "ddim", lead_dp(ctx), lead_dp(lats), None, gs, None,
            progress=False, gate=None, metrics=False),
        "sweep/phase1-mesh": _sweep_phase1_jit.lower(
            pipe.unet_params, cfg, layout, schedule, "ddim",
            lead_dp(ctx), lead_dp(lats), lead_dp(ctrl), gs,
            progress=False, gate=GATE, metrics=False),
        "sweep/phase2-mesh": _sweep_phase2_jit.lower(
            pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
            "ddim", lead_dp(cond_b), lead_dp(carry), lead_dp(p2), gs,
            progress=False, gate=GATE, metrics=False),
    }
    return {name: low.as_text() for name, low in lowerings.items()}


def check_donation(pipe=None,
                   declared: Optional[Dict[str, Tuple[int, ...]]] = None,
                   lowerings: Optional[Dict[str, str]] = None,
                   ) -> List[ContractResult]:
    """Lower every declared jitted entry point (monolithic, pool, and mesh
    programs) and check buffer donation against :data:`DECLARED_DONATION`
    — both directions (declared-but-absent and applied-but-undeclared
    fail). ``declared``/``lowerings`` are injection points for the seeded
    verdict-flip proofs in tests/test_jaxcheck.py."""
    if declared is None:
        declared = DECLARED_DONATION
    if lowerings is None:
        if pipe is None:
            pipe = tiny_pipeline()
        lowerings = _donation_lowerings(pipe)
    out = []
    for name, wants in declared.items():
        text = lowerings.get(name)
        if text is None:
            out.append(ContractResult(
                "donation-as-declared", name, False,
                "declared program has no lowering in the sweep (stale "
                "DECLARED_DONATION entry?)"))
            continue
        n = _donated_params(text)
        ok = (n > 0) == (len(wants) > 0)
        detail = (f"{n} donated param(s) in lowering, "
                  f"{len(wants)} declared")
        out.append(ContractResult("donation-as-declared", name, ok, detail))
    return out


def run_contracts(pipe=None, buckets=(1, 2, 4, 8)) -> List[ContractResult]:
    """All jaxpr contracts over all canonical programs (telemetry off and
    on), plus the donation check. The compile-key completeness sweep lives
    in :mod:`.compile_key` (it needs per-Request tracing, not the canonical
    set)."""
    if pipe is None:
        pipe = tiny_pipeline()
    plain = canonical_programs(pipe, buckets=buckets, metrics=False)
    instrumented = canonical_programs(pipe, buckets=buckets[:1],
                                      metrics=True)
    results: List[ContractResult] = []
    results += check_no_f64(plain)
    results += check_hot_scan_callbacks(plain)
    results += check_hot_scan_callbacks(instrumented)
    results += check_phase2_footprint(plain)
    results += check_pool_footprint(plain)
    results += check_donation(pipe)
    # Kernel-bearing twins (ISSUE 16): the fused-edit dispatch programs are
    # canonical too — they carry every structural contract the materialized
    # programs do, plus the no-materialized-probs proof against their
    # kernels=None twins.
    kpairs = kernel_programs(pipe)
    fused = [p for p in kpairs if p.name.endswith("-fused")]
    results += check_no_f64(kpairs)
    results += check_hot_scan_callbacks(fused)
    results += check_phase2_footprint(fused)
    results += check_no_materialized_probs(kpairs)
    # Flight tracing joins the disabled-invisible sweep at one bucket
    # (the check retraces the canonical set twice; the program identity
    # property is bucket-independent).
    results += check_trace_invisible(pipe, buckets=buckets[:1])
    return results
