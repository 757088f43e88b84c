"""Data-parallel sweeps: many edit groups at once across the mesh.

The reference's CLI loops 10 seeds sequentially on one GPU
(`/root/reference/main.py:417-444`); its equalizer sweep is a batch on one
device (`/root/reference/main.py:281-290`). Here both become one
``jax.vmap``-over-groups program sharded over the mesh's ``dp`` axis: each
device holds whole edit groups (the base-prompt/edit-prompt co-location
constraint, SURVEY §2), the sampling loop runs with **zero collectives**, and
results gather once at the end. Group-count per call is static; sweep values
(seeds, equalizer scales, thresholds, step windows) are traced leaves, so a
new sweep re-uses the compiled program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..controllers.base import AttnLayout, Controller
from ..engine.sampler import (PhaseCarry, _denoise_scan, _phase1_scan,
                              _phase2_scan, _schedule_of, resolve_gate,
                              resolve_reuse, stage_host, warn_gate_truncation)
from ..models import nn
from ..models import vae as vae_mod
from ..models.conditioning import rows
from ..models.config import PipelineConfig
from ..obs import launches
from ..obs.spans import span
from ..ops import schedulers as sched_mod


@partial(jax.jit, static_argnames=("cfg", "layout", "scheduler_kind",
                                   "progress", "gate", "metrics", "reuse",
                                   "kernels", "mesh"),
         donate_argnums=())
def _sweep_jit(
    unet_params: Any,
    vae_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context: jax.Array,        # (G, 2B, L, D) per-group [uncond; cond]
    latents: jax.Array,        # (G, B, h, w, c)
    controllers: Optional[Controller],   # leaves with leading G axis (or None)
    guidance_scale: jax.Array,
    uncond_per_step: Optional[jax.Array],  # (G, T, 1, L, D) or None
    progress: bool = False,
    gate: Optional[int] = None,
    metrics: bool = False,
    reuse=None,
    kernels=None,
    mesh: Optional[Mesh] = None,
):
    def one_group(ctx, lat, ctrl, ups):
        # The scanned step index is vmap-invariant (built inside the scan,
        # independent of the batched inputs), so the progress callback fires
        # once per step — not once per group. The same holds for the
        # telemetry callback (metrics=True).
        lat, state = _denoise_scan(
            unet_params, cfg, layout, schedule, scheduler_kind, ctx, lat, ctrl,
            guidance_scale, uncond_per_step=ups, progress=progress, gate=gate,
            metrics=metrics, reuse=reuse, kernels=kernels)
        image = vae_mod.decode(vae_params, cfg.vae, lat.astype(jnp.float32))
        return vae_mod.to_uint8(image), lat

    return _vmap_groups(one_group, mesh)(context, latents, controllers,
                                         uncond_per_step)


def _vmap_groups(one_group, mesh: Optional[Mesh]):
    """``vmap`` over the group axis. Under a mesh that axis is the one the
    inputs are sharded on (``dp``), and the Pallas kernels in the body run
    per device (``nn.kernel_mesh``) — the partitioner cannot split them."""
    if mesh is None:
        return jax.vmap(one_group)

    def groups(*args):
        with nn.kernel_mesh(mesh):
            return jax.vmap(one_group, spmd_axis_name="dp")(*args)

    return groups


def _stage_replicated(tree, mesh: Mesh):
    """Stage a pytree's array leaves mesh-replicated — the explicit form
    of what pjit would otherwise do *implicitly* at dispatch for shared
    traced values (the schedule's constant tables). The tables are tiny
    (a few (num_train,) vectors), so per-call staging is noise; what
    matters is that the transfer is explicit and therefore passes the
    serve layer's ``jax.transfer_guard("disallow")`` contract on mesh
    dispatch."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: _stage_sharded(x, rep), tree)


def _stage_sharded(x, gspec: NamedSharding):
    """Put a host-replicated value onto the mesh under ``gspec``.

    Single-process: plain ``jax.device_put``. Multi-process: modern jax's
    ``device_put`` of an unsharded value onto a multihost sharding runs a
    cross-host ``assert_equal`` collective (``broadcast_one_to_all``) that
    the CPU gloo backend cannot execute ("Multiprocess computations aren't
    implemented on the CPU backend" — the test_multihost_2proc drift).
    Every process already holds the identical full value (seeded
    identically by construction), so each just donates its own addressable
    shards via ``make_array_from_callback`` — no collective at all, and
    bitwise the same global array."""
    if jax.process_count() <= 1:
        return jax.device_put(x, gspec)
    x_np = np.asarray(x)
    return jax.make_array_from_callback(x_np.shape, gspec,
                                        lambda idx: x_np[idx])


def sweep(
    pipe,
    context: jax.Array,
    latents: jax.Array,
    controllers: Optional[Controller],
    *,
    num_steps: int = 50,
    guidance_scale: float = 7.5,
    scheduler: str = "ddim",
    layout: Optional[AttnLayout] = None,
    mesh: Optional[Mesh] = None,
    uncond_per_step: Optional[jax.Array] = None,
    progress: bool = False,
    gate=None,
    metrics: bool = False,
    lower_only: bool = False,
    schedule=None,
    kernels=None,
) -> Tuple[jax.Array, jax.Array]:
    """Run G independent edit groups; shard the group axis over ``dp``.

    ``context``: (G, 2B, L, D), or the preset's ``Conditioning`` with those
    leading axes on every leaf; ``latents``: (G, B, h, w, c);
    ``controllers``: a Controller pytree whose array leaves carry a leading
    G axis (same static structure per group — e.g. one edit with G equalizer
    rows or G cross-window schedules), or None.

    ``uncond_per_step``: optional (G, T, 1, L, D) per-group null-text
    embeddings (``InversionArtifact.uncond_embeddings`` stacked — or
    broadcast — over the group axis), substituted for the uncond half of
    ``context`` at each step exactly as in ``text2image``: an inverted real
    image's edit sweep rides the same zero-collective dp engine as a seed
    sweep (the missing-notebook workflow, `/root/reference/null_text.py:618`
    + SURVEY §3.2, at mesh scale). DDIM-only, like the sequential path.
    ``gate`` enables phase-gated sampling exactly as in ``text2image``
    (``engine.sampler.resolve_gate`` semantics; ``'auto'`` resolves against
    the stacked controllers' max edit window); incompatible with
    ``uncond_per_step`` for the same null-text-window reason.
    Negative-prompt contexts need no parameter here: the uncond rows of
    ``context`` are caller-encoded, so a per-group negative prompt is just
    a different uncond half. ``progress=True`` reports per-step progress
    exactly like ``text2image`` (the scanned step index is group-invariant,
    so the sweep emits one callback per step). ``metrics=True`` traces the
    phase-tagged telemetry callback in exactly as in ``text2image`` —
    ``obs.device.instrument`` collects it; disabled, the program is
    unchanged. Returns ``(images (G,B,H,W,3) uint8, final latents)``.

    ``lower_only=True`` returns the ``jax.stages.Lowered`` for this exact
    program instead of executing it — the cost observatory's entry point
    (``obs.costmodel``): ``.compile()`` on the result yields the XLA
    ``cost_analysis()``/``memory_analysis()`` the cost cards are built
    from. Nothing is staged onto a device in this mode (the program is
    lowered mesh-less: a cost card describes the logical computation;
    the scope scales peaks by the device count separately).

    ``kernels`` (a static :class:`p2p_tpu.kernels.KernelConfig`, or None)
    routes covered controller-edited attention sites to the fused-edit
    Pallas kernel exactly as in ``text2image`` — the edit applied inside
    the attention tile, per group, under the same vmap-over-groups program.
    """
    with span("entry.sweep"):
        cfg = pipe.config
        if layout is None:
            from ..models.config import denoiser_layout
            layout = denoiser_layout(cfg.unet)
        controllers = layout.resolve(controllers)
        # a sweep returns no store: slots for LocalBlend's maps alone
        layout = layout.for_readers(controllers)
        if uncond_per_step is not None:
            if scheduler != "ddim":
                # Same constraint as text2image: the embeddings are optimized
                # against the DDIM trajectory (`/root/reference/null_text.py:23`).
                raise ValueError("uncond_per_step requires scheduler='ddim'")
            if (uncond_per_step.ndim != 5
                    or uncond_per_step.shape[0] != rows(context)):
                raise ValueError(
                    f"uncond_per_step must be (G, T, 1, L, D) with G="
                    f"{rows(context)}, got {uncond_per_step.shape}")
            if uncond_per_step.shape[1] != num_steps:
                raise ValueError(
                    f"uncond_per_step has {uncond_per_step.shape[1]} steps, "
                    f"sampling uses {num_steps}")
        groups = rows(context)
        with span("entry.prepare", steps=int(num_steps), batch=groups):
            tsched = sched_mod.schedule_from_config(num_steps, cfg.scheduler,
                                                    kind=scheduler)
            num_scan = tsched.timesteps.shape[0]
            # ``schedule`` (a reuse-schedule spec / resolved table — ISSUE 15)
            # generalizes ``gate``; resolve_reuse enforces mutual exclusion,
            # keys a uniform table as its gate and fires the per-site
            # window-conflict warning for the others.
            gate_step, reuse_sched = resolve_reuse(gate, schedule, layout,
                                                   num_scan, controllers)
        if gate_step < num_scan and uncond_per_step is not None:
            raise ValueError(
                f"gate={gate!r} conflicts with per-step null-text uncond "
                "embeddings (active through every step): run null-text replay "
                "sweeps with gate=None")
        if reuse_sched is not None and uncond_per_step is not None:
            raise ValueError(
                "schedule conflicts with per-step null-text uncond embeddings:"
                " run null-text replay sweeps with schedule=None")
        # Same surfaced semantics as the sequential path: an explicit gate that
        # truncates edit windows / freezes an explicit store must not be
        # silent just because the run is batched.
        if reuse_sched is None:
            warn_gate_truncation(gate_step, num_scan, controllers)
        schedule = tsched
        # Explicit staging when the scale arrives as a host scalar: the serve
        # loop dispatches under jax.transfer_guard("disallow"), where an
        # implicit jnp.asarray(float) h2d would raise (already-on-device values
        # pass through untouched). On a mesh the scalar stages replicated
        # under an explicit NamedSharding (same contract, mesh form).
        if lower_only:
            # Cost-card path: lower the exact program (same static args, same
            # avals) without staging or executing anything. A concrete host
            # scalar stands in for the staged guidance — same dtype/shape, so
            # the lowered HLO is the dispatched program's.
            return _sweep_jit.lower(
                pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
                scheduler, context, latents, controllers,
                np.float32(guidance_scale), uncond_per_step,
                progress=progress, gate=gate_step, metrics=metrics,
                reuse=reuse_sched, kernels=kernels)
        with span("entry.prepare", batch=groups):        # staging
            gs = (guidance_scale if isinstance(guidance_scale, jax.Array)
                  else stage_host(np.float32(guidance_scale), mesh=mesh))

            if mesh is not None:
                gspec = NamedSharding(mesh, P("dp"))
                context, latents, controllers, uncond_per_step = jax.tree.map(
                    lambda x: _stage_sharded(x, gspec),
                    (context, latents, controllers, uncond_per_step))
                schedule = _stage_replicated(schedule, mesh)

        if progress:
            from ..utils import progress as progress_mod

            progress_mod.activate(schedule.timesteps.shape[0],
                                  f"sweep x{groups}")

        with span("sampler.sweep", groups=groups,
                  steps=int(schedule.timesteps.shape[0]), gate=int(gate_step)):
            args = (pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
                    scheduler, context, latents, controllers, gs,
                    uncond_per_step)
            kwargs = dict(progress=progress, gate=gate_step, metrics=metrics,
                          reuse=reuse_sched, kernels=kernels, mesh=mesh)
            mark = launches.built()
            out = _sweep_jit(*args, **kwargs)
            launches.keep_if_built(mark, _sweep_jit, args, kwargs)
            return out


@partial(jax.jit, static_argnames=("cfg", "layout", "scheduler_kind",
                                   "progress", "gate", "metrics", "reuse",
                                   "kernels", "mesh"),
         donate_argnums=())
def _sweep_phase1_jit(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context: jax.Array,        # (G, 2B, L, D) per-group [uncond; cond]
    latents: jax.Array,        # (G, B, h, w, c)
    controllers: Optional[Controller],   # leaves with leading G axis (or None)
    guidance_scale: jax.Array,
    progress: bool = False,
    gate: int = 1,
    metrics: bool = False,
    reuse=None,
    kernels=None,
    mesh: Optional[Mesh] = None,
) -> PhaseCarry:
    """The serve layer's phase-1 POOL program: steps ``[0, gate)`` of G
    groups under full CFG + controller hooks, returning the per-group
    :class:`~p2p_tpu.engine.sampler.PhaseCarry` (leaves carry a leading G
    axis) instead of images — no VAE decode, the trajectory continues in a
    separately scheduled phase-2 program. ``reuse`` (a non-uniform
    ``engine.reuse`` table, static) generalizes the gate: the carry's
    cache holds the schedule's leaf set instead of all-cross."""
    sched = _schedule_of(gate, reuse, layout, schedule.timesteps.shape[0])

    def one_group(ctx, lat, ctrl):
        return _phase1_scan(unet_params, cfg, layout, schedule,
                            scheduler_kind, ctx, lat, ctrl, guidance_scale,
                            reuse=sched, progress=progress, metrics=metrics,
                            kernels=kernels)

    return _vmap_groups(one_group, mesh)(context, latents, controllers)


@partial(jax.jit, static_argnames=("cfg", "layout", "scheduler_kind",
                                   "progress", "gate", "metrics", "reuse",
                                   "kernels", "mesh"),
         donate_argnums=())
def _sweep_phase2_jit(
    unet_params: Any,
    vae_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context_cond: jax.Array,   # (G, B, L, D) — cond half only, no uncond
    carry: PhaseCarry,         # leaves with leading G axis
    controllers: Optional[Controller],   # phase-2 slice, G-leading (or None)
    guidance_scale: jax.Array,
    progress: bool = False,
    gate: int = 1,
    metrics: bool = False,
    reuse=None,
    kernels=None,
    mesh: Optional[Mesh] = None,
):
    """The serve layer's phase-2 POOL program: steps ``[gate, S)`` of G
    hand-off carries — single-branch U-Net off the AttnCache, fixed-
    extrapolation guidance, then the VAE decode. The G lanes may come from
    *different* requests (different phase-1 batches): everything request-
    specific rides the carry and the cond context. Returns
    ``(images (G,B,H,W,3) uint8, final latents)``."""
    sched = _schedule_of(gate, reuse, layout, schedule.timesteps.shape[0])

    def one_group(ctx_c, car, ctrl):
        lat = _phase2_scan(unet_params, cfg, layout, schedule,
                           scheduler_kind, ctx_c, car, ctrl, guidance_scale,
                           reuse=sched, progress=progress, metrics=metrics,
                           kernels=kernels)
        image = vae_mod.decode(vae_params, cfg.vae, lat.astype(jnp.float32))
        return vae_mod.to_uint8(image), lat

    return _vmap_groups(one_group, mesh)(context_cond, carry, controllers)


def _phase_args(pipe, num_steps: int, scheduler: str, gate,
                guidance_scale, layout, controllers, mesh=None,
                schedule=None):
    """Shared wrapper plumbing for the two pool entry points: schedule,
    resolved+validated gate (a pool program needs both phases non-empty),
    staged guidance (replicated over ``mesh`` when given), the controllers
    with their defaults taken against the layout (``AttnLayout.resolve``) and
    the layout with store slots for their readers (``AttnLayout.for_readers``).
    ``schedule`` is a reuse-schedule spec/table (ISSUE 15): its
    ``cfg_gate`` is the pool boundary; a uniform table is keyed as its
    gate."""
    with span("entry.prepare"):
        cfg = pipe.config
        if layout is None:
            from ..models.config import denoiser_layout
            layout = denoiser_layout(cfg.unet)
        controllers = layout.resolve(controllers)
        # as in ``sweep``; phase 1's full controller and phase 2's slice name
        # the same blend, so both programs get the hand-off store's shapes
        layout = layout.for_readers(controllers)
        dsched = sched_mod.schedule_from_config(num_steps, cfg.scheduler,
                                                kind=scheduler)
        num_scan = dsched.timesteps.shape[0]
        gate_step, reuse_sched = resolve_reuse(gate, schedule, layout, num_scan,
                                               controllers)
        if not 1 <= gate_step < num_scan:
            raise ValueError(
                f"a phase pool program needs a real gate: resolved gate step "
                f"{gate_step} of {num_scan} leaves a phase empty — ungated "
                "requests take the single-pool sweep() path")
        gs = (guidance_scale if isinstance(guidance_scale, jax.Array)
              else stage_host(np.float32(guidance_scale), mesh=mesh))
        return cfg, layout, dsched, gate_step, gs, reuse_sched, controllers


def sweep_phase1(
    pipe,
    context: jax.Array,
    latents: jax.Array,
    controllers: Optional[Controller],
    *,
    num_steps: int = 50,
    guidance_scale: float = 7.5,
    scheduler: str = "ddim",
    layout: Optional[AttnLayout] = None,
    mesh: Optional[Mesh] = None,
    gate=None,
    progress: bool = False,
    metrics: bool = False,
    lower_only: bool = False,
    schedule=None,
    kernels=None,
) -> PhaseCarry:
    """Run phase 1 of G groups (same shapes/semantics as :func:`sweep`) and
    return the hand-off carry instead of images. ``gate`` must resolve
    strictly inside ``(0, S)``. ``mesh`` shards the group axis over ``dp``
    exactly as in :func:`sweep` — the returned carry leaves come out
    sharded the same way (the hand-off stays on device).
    ``lower_only=True`` returns the program's ``Lowered`` instead of
    executing (the cost-card path — see :func:`sweep`)."""
    with span("entry.sweep_phase1"):
        cfg, layout, dsched, gate_step, gs, reuse_sched, controllers = _phase_args(
            pipe, num_steps, scheduler, gate, guidance_scale, layout,
            controllers, mesh=mesh, schedule=schedule)
        if reuse_sched is None:
            warn_gate_truncation(gate_step, dsched.timesteps.shape[0],
                                 controllers)
        schedule = dsched
        if lower_only:
            return _sweep_phase1_jit.lower(
                pipe.unet_params, cfg, layout, schedule, scheduler, context,
                latents, controllers, np.float32(guidance_scale),
                progress=progress, gate=gate_step, metrics=metrics,
                reuse=reuse_sched, kernels=kernels)
        if mesh is not None:
            gspec = NamedSharding(mesh, P("dp"))
            context, latents, controllers = jax.tree.map(
                lambda x: _stage_sharded(x, gspec),
                (context, latents, controllers))
            schedule = _stage_replicated(schedule, mesh)
        with span("sampler.sweep_phase1", groups=rows(context),
                  steps=int(schedule.timesteps.shape[0]), gate=int(gate_step)):
            args = (pipe.unet_params, cfg, layout, schedule, scheduler,
                    context, latents, controllers, gs)
            kwargs = dict(progress=progress, gate=gate_step, metrics=metrics,
                          reuse=reuse_sched, kernels=kernels, mesh=mesh)
            mark = launches.built()
            out = _sweep_phase1_jit(*args, **kwargs)
            launches.keep_if_built(mark, _sweep_phase1_jit, args, kwargs)
            return out


def sweep_phase2(
    pipe,
    context_cond: jax.Array,
    carry: PhaseCarry,
    controllers: Optional[Controller],
    *,
    num_steps: int = 50,
    guidance_scale: float = 7.5,
    scheduler: str = "ddim",
    layout: Optional[AttnLayout] = None,
    mesh: Optional[Mesh] = None,
    gate=None,
    progress: bool = False,
    metrics: bool = False,
    lower_only: bool = False,
    schedule=None,
    kernels=None,
) -> Tuple[jax.Array, jax.Array]:
    """Finish G hand-off carries: steps ``[gate, S)`` + VAE decode.
    ``controllers`` must already be the phase-2 slice
    (``engine.sampler.phase2_controller``, stacked over G — or None);
    passing a full edit controller here would silently split pools that
    could share one program. ``mesh`` shards the packed carry batch over
    ``dp``: re-packed hand-off lanes (already on device, possibly from
    different phase-1 batches on different shards) are staged to their
    target shard with an explicit device-to-device ``device_put`` — no
    host round-trip, so the transfer-guard("disallow") contract holds on
    mesh dispatch too. Returns ``(images, final latents)``."""
    with span("entry.sweep_phase2"):
        cfg, layout, schedule, gate_step, gs, reuse_sched, controllers = _phase_args(
            pipe, num_steps, scheduler, gate, guidance_scale, layout,
            controllers, mesh=mesh, schedule=schedule)
        if lower_only:
            return _sweep_phase2_jit.lower(
                pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
                scheduler, context_cond, carry, controllers,
                np.float32(guidance_scale), progress=progress, gate=gate_step,
                metrics=metrics, reuse=reuse_sched, kernels=kernels)
        if mesh is not None:
            gspec = NamedSharding(mesh, P("dp"))
            context_cond, carry, controllers = jax.tree.map(
                lambda x: _stage_sharded(x, gspec),
                (context_cond, carry, controllers))
            schedule = _stage_replicated(schedule, mesh)
        with span("sampler.sweep_phase2", groups=rows(context_cond),
                  steps=int(schedule.timesteps.shape[0]), gate=int(gate_step)):
            args = (pipe.unet_params, pipe.vae_params, cfg, layout, schedule,
                    scheduler, context_cond, carry, controllers, gs)
            kwargs = dict(progress=progress, gate=gate_step, metrics=metrics,
                          reuse=reuse_sched, kernels=kernels, mesh=mesh)
            mark = launches.built()
            out = _sweep_phase2_jit(*args, **kwargs)
            launches.keep_if_built(mark, _sweep_phase2_jit, args, kwargs)
            return out


def artifact_replay_inputs(pipe, x_t, uncond_embeddings, source: str,
                           targets, controllers):
    """Build the ``sweep`` inputs that replay one inversion artifact across
    G target edits: ``(ctx_g, lats, ups, ctrls)``.

    ``x_t``/``uncond_embeddings``/``source`` come from an
    ``InversionArtifact``; ``controllers`` is one Controller per target
    (same static structure — one edit mode for all). One text-encoder
    forward covers every prompt; the terminal latent and per-step null
    embeddings broadcast over the group axis. Shared by
    ``p2p-tpu replay --batch-targets`` and
    ``examples/null_text_w_ptp.py`` step 5."""
    from ..engine.sampler import encode_prompts

    g = len(targets)
    if len(controllers) != g:
        raise ValueError(f"{len(controllers)} controllers for {g} targets")
    ctrls = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *controllers)
    enc = encode_prompts(pipe, ["", source] + list(targets))
    # per group [uncond, uncond, source, target i]: rows of one encode
    pick = np.asarray([[0, 0, 1, 2 + i] for i in range(g)])
    ctx_g = jax.tree.map(lambda e: e[pick], enc)
    x_t = jnp.asarray(x_t)
    lats = jnp.broadcast_to(x_t[None], (g, 2) + x_t.shape[1:])
    ups = jnp.broadcast_to(jnp.asarray(uncond_embeddings)[None],
                           (g,) + tuple(uncond_embeddings.shape))
    return ctx_g, lats, ups, ctrls


def seed_latents(rng: jax.Array, n_groups: int, group_batch: int,
                 shape: Tuple[int, int, int], dtype=jnp.float32) -> jax.Array:
    """One shared latent per group, expanded over the group's prompt batch
    (`/root/reference/ptp_utils.py:88-95` per group)."""
    base = jax.random.normal(rng, (n_groups, 1) + tuple(shape), dtype=dtype)
    return jnp.broadcast_to(base, (n_groups, group_batch) + tuple(shape))
