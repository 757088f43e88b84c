"""The sampling engine: jit-compiled text→image with attention control.

Behavioral spec: `/root/reference/ptp_utils.py:65-172` (`diffusion_step`,
`text2image_ldm_stable`, `init_latent`, `latent2image`). TPU re-design:

- The T-step denoising loop is a single ``lax.scan`` whose carry is
  ``(latents, controller store state, PLMS multistep state)`` — the step index
  arrives from the scanned-over ``(step, timestep)`` pair, replacing the
  reference's ``cur_step`` mutation.
- CFG rides batch-doubling exactly as `/root/reference/ptp_utils.py:70-73`:
  one U-Net call on ``[uncond; cond]`` of batch 2B. (The reference's
  ``low_resource`` two-call variant is a GPU-memory workaround we don't need;
  see `/root/reference/ptp_utils.py:66-68`.)
- The controller is a pytree *argument* of the jitted function: edit
  parameters, thresholds and step windows are traced leaves, so sweeping them
  reuses one compiled program. Controller *structure* (kind, which sites are
  touched) is static and changes the program — the identity controller
  compiles to a plain sampler with zero hook overhead.
- The shared-seed expansion of `/root/reference/ptp_utils.py:88-95` (all
  prompts in an edit group start from ONE latent — essential to P2P) lives in
  :func:`init_latent`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import TYPE_CHECKING, Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # the sp plan type; runtime stays import-cycle-free
    from ..models.unet import SpConfig

from ..controllers.base import (
    AttnLayout,
    Controller,
    StoreState,
    apply_step_callback,
    controller_step_window,
    init_store_state,
)
from ..models import vae as vae_mod
from ..models.conditioning import (Caption, cfg_rows, context_of, live_keys,
                                   with_context)
from ..models.config import PipelineConfig
from ..models.denoiser import denoise, prepare, require_unet
from ..models.text_encoder import apply_text_encoder, apply_text_towers
from ..obs import launches
from ..obs.spans import span
from ..ops import schedulers as sched_mod
from ..utils import progress as progress_mod
from ..utils.tokenizer import Tokenizer, pad_ids
from . import reuse as reuse_mod


@dataclasses.dataclass(frozen=True)
class Pipeline:
    """A bound backend: config + parameter pytrees. The analogue of the
    reference's `StableDiffusionPipeline` handle (`/root/reference/main.py:29`),
    but immutable — controllers are sampling-call arguments, never installed
    into the model."""

    config: PipelineConfig
    unet_params: Any
    text_params: Any      # one tower's tree, or a list of trees (config.text)
    vae_params: Any
    tokenizer: Tokenizer

    @property
    def latent_shape(self) -> Tuple[int, int, int]:
        s = self.config.latent_size
        return (s, s, self.config.unet.in_channels)


@partial(jax.jit, static_argnames=("cfg", "dtype"))
def _encode_jit(text_params, cfg, ids, dtype, eos=None, mask=None):
    """``cfg`` is ``PipelineConfig.text``: one tower's configuration, or a
    tuple of them (then ``eos`` (B,) is each prompt's end-of-text position,
    where the pooled text is read). A tower that attends under a key mask
    (``mask`` (B, L) int32, a ``t5`` tower's) gives a ``Caption`` of its
    hidden states and the mask."""
    if isinstance(cfg, tuple):
        return apply_text_towers(text_params, cfg, ids, eos, dtype=dtype)
    if mask is not None:
        return Caption(apply_text_encoder(text_params, cfg, ids, dtype=dtype,
                                          mask=mask), mask)
    return apply_text_encoder(text_params, cfg, ids, dtype=dtype)


def stage_host(x, mesh=None):
    """Explicitly stage a host value onto the device(s) — the h2d form
    that passes ``jax.transfer_guard("disallow")``, which the serve
    dispatch hot path runs under (tests/test_serve.py).

    ``mesh`` (a ``jax.sharding.Mesh``) stages the value *replicated over
    the mesh* via an explicit ``NamedSharding`` — the mesh-dispatch form
    of the same contract, so sharded serve programs receive their
    host-born scalars (seeds, guidance) without an implicit per-device
    broadcast (pinned under the virtual 8-device mesh by
    tests/test_serve_mesh.py). On a *multiprocess* mesh ``jax.device_put``
    of an unsharded value runs a cross-host equality collective the CPU
    backend can't execute, so multihost runs keep the implicit path —
    there the transfer-guard contract is explicitly out of scope
    (single-process serving property; see ``parallel.sweep._stage_sharded``
    for the collective-free multihost staging of *sharded* values)."""
    if jax.process_count() > 1:
        return jnp.asarray(x)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
    return jax.device_put(x)


def encode_prompts(pipe: Pipeline, prompts, dtype=jnp.float32):
    """Tokenize + encode to the preset's conditioning
    (``models.conditioning``): (B, L, D) hidden states
    (`/root/reference/ptp_utils.py:144-156`), or for a preset of several
    towers a ``Conditioning`` of their concatenated states and the pooled
    text, or for a ``t5`` tower a ``Caption`` of its states and their key
    mask: every token up to and including the first end-of-text id."""
    tok = pipe.tokenizer
    max_len = pipe.config.unet.context_len
    with span("entry.tokenize", prompts=len(prompts)):
        ids = np.asarray(
            [pad_ids(tok.encode(p), max_len,
                     getattr(tok, "pad_token_id", tok.eos_token_id))
             for p in prompts], dtype=np.int32)
    with span("entry.encode", prompts=len(prompts), tokens=int(ids.size)):
        # Token ids are the one host-born input of every dispatch: staged
        # explicitly (stage_host) so the serve hot path stays clean under
        # jax.transfer_guard("disallow").
        args = (pipe.text_params, pipe.config.text, stage_host(ids), dtype)
        kwargs = {}
        if isinstance(pipe.config.text, tuple):
            # first end-of-text id of each prompt (padding repeats it)
            kwargs["eos"] = stage_host(
                (ids == tok.eos_token_id).argmax(axis=1).astype(np.int32))
        elif pipe.config.text.arch == "t5":
            # no end-of-text id before the key: the first one and all ahead
            eos = ids == tok.eos_token_id
            kwargs["mask"] = stage_host(
                (np.cumsum(eos, axis=1) - eos == 0).astype(np.int32))
        mark = launches.built()
        out = _encode_jit(*args, **kwargs)
        launches.keep_if_built(mark, _encode_jit, args, kwargs)
        return out


def init_latent(latent: Optional[jax.Array], shape: Tuple[int, ...], rng: jax.Array,
                batch: int, dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """One latent expanded over the edit group
    (`/root/reference/ptp_utils.py:88-95`). Returns (single, batched)."""
    if latent is None:
        latent = jax.random.normal(rng, (1,) + tuple(shape), dtype=dtype)
    latents = jnp.broadcast_to(latent, (batch,) + tuple(latent.shape[1:])).astype(dtype)
    return latent, latents


def lane_select(outputs, lanes):
    """Batch-lane masking hook for the serving layer.

    A padded serve batch runs ``sweep`` with ``G = bucket`` lanes of which
    only the first ``len(lanes)`` carry real requests (padding replicates a
    real lane; a poisoned lane is dropped on the isolation retry). This is
    the single place lane → request resolution happens: it gathers the
    selected lanes of a ``(G, ...)`` output to host numpy, so padded or
    masked-out lanes can never leak into a response record.
    """
    import numpy as np

    out = np.asarray(outputs)
    return [out[i] for i in lanes]


@jax.jit
def _lane_finite_jit(x):
    # f32 view: bf16/f16 lanes reduce identically and uint8 is trivially
    # finite (which is why validation runs on latents, pre-decode).
    xf = x.astype(jnp.float32)
    return jnp.all(jnp.isfinite(xf), axis=tuple(range(1, xf.ndim)))


def lane_finite(outputs):
    """Output-validation hook for the serving layer: one finite flag per
    leading-axis lane of a ``(G, ...)`` float array (final latents of a
    padded sweep batch).

    A NaN/Inf-poisoned lane decodes to a black or garbage image that would
    otherwise ship as a healthy ``ok`` record — this is the single reduction
    that catches it. It is a separate tiny jitted program applied to the
    sweep's *output*, so the sampling program itself is identical whether
    validation runs or not (the serve layer's disabled-mode contract), and
    the cost is one all-reduce per lane, off the denoising hot path.
    """
    import numpy as np

    return np.asarray(_lane_finite_jit(jnp.asarray(outputs)))


def resolve_gate(gate, num_scan_steps: int,
                 controller: Optional[Controller] = None) -> int:
    """Resolve a user-facing ``gate`` spec to a static scan-step index.

    ``None`` (or the full step count) disables phase-gated sampling. A float
    in ``(0, 1]`` is a fraction of the scan length; an int is the scan step
    where phase 2 begins (≥ 1 — the cache needs at least one phase-1 step).
    ``'auto'`` resolves to ``max(S // 2, controller edit-window end, 1)`` —
    the SD-Acc midpoint, but never truncating inside an active edit window
    (`controllers.base.controller_step_window`).
    """
    s = num_scan_steps
    if gate is None:
        return s
    if gate == "auto":
        return min(s, max(s // 2, controller_step_window(controller, s), 1))
    if isinstance(gate, float):
        if not 0.0 < gate <= 1.0:
            raise ValueError(f"fractional gate must be in (0, 1], got {gate}")
        g = int(round(gate * s))
    elif isinstance(gate, int):
        g = gate
    else:
        raise ValueError(f"gate must be None, 'auto', a float fraction or an "
                         f"int step, got {gate!r}")
    if not 1 <= g <= s:
        raise ValueError(f"gate step {g} outside [1, {s}]")
    return g


def resolve_reuse(gate, schedule, layout, num_scan: int,
                  controller: Optional[Controller] = None):
    """Resolve the (``gate``, ``schedule``) pair every sampling surface
    accepts into ``(gate_step, reuse_or_None)``.

    ``schedule`` is a reuse-schedule spec (JSON dict), an already-resolved
    ``engine.reuse.ReuseSchedule``, or None. The two knobs are mutually
    exclusive — a schedule IS a generalized gate. This pair is what the
    jitted entry points and the serve layer's compile keys are keyed on, so
    one run has one form: a schedule that resolves to the UNIFORM table is
    returned as its gate step (``reuse=None``) and compiles and pools as
    ``gate=g`` does (under the entry points both are the same table again:
    :func:`_schedule_of`). Other schedules return the static table; the
    per-site window-conflict warning fires here (the generalized
    ``warn_gate_truncation``)."""
    if schedule is None:
        return resolve_gate(gate, num_scan, controller), None
    if gate is not None:
        raise ValueError("gate and schedule are mutually exclusive: a "
                         "reuse schedule generalizes the gate (its "
                         "cfg_gate is the phase boundary)")
    sched = reuse_mod.resolve_schedule(schedule, layout, num_scan,
                                       controller)
    u = sched.uniform_gate
    if u is not None:
        warn_gate_truncation(u, num_scan, controller)
        return u, None
    reuse_mod.warn_schedule_conflicts(sched, layout, controller, num_scan)
    return sched.cfg_gate, sched


def warn_gate_truncation(gate_step: int, num_scan: int,
                         controller: Optional[Controller]) -> None:
    """Warn when an explicit gate changes controller semantics: truncating
    inside an active edit window, or freezing an explicit attention store.
    Shared by the sequential (``text2image``) and batched (``sweep``) paths
    so both surfaces report the same conditions the same way."""
    if gate_step >= num_scan:
        return
    import warnings

    window = controller_step_window(controller, num_scan)
    if gate_step < window:
        warnings.warn(
            f"gate step {gate_step} truncates inside the controller's "
            f"edit window (ends at {window}): attention edits past the "
            "gate are dropped. Use gate='auto' to clamp to the window.",
            stacklevel=3)
    if controller is not None and controller.store:
        warnings.warn(
            f"gate step {gate_step} < {num_scan}: the attention store "
            "stops accumulating at the gate, so averaged maps cover "
            "phase 1 only", stacklevel=3)


class PhaseCarry(NamedTuple):
    """The phase-1 → phase-2 hand-off, packaged as ONE pytree.

    This is the unit of transfer between the serve layer's two program
    pools (phase-disaggregated continuous batching): everything a phase-2
    program needs to continue a trajectory whose CFG/controller phase
    already ran. The treedef is *pinned* per compiled program —
    :func:`carry_spec` renders it (structure + leaf shapes/dtypes) and the
    hand-off path validates it, so a carry can never silently feed a
    mismatched phase-2 program. All leaves are plain arrays, so a carry
    round-trips through host memory (``jax.device_get`` → ``.npz`` → device)
    for the journal's crash-replay spill.
    """

    latents: jax.Array    # (B, h, w, c) latents after the last phase-1 step
    resid: jax.Array      # (B, h, w, c) CFG residual ε_text − ε_uncond there
    cache: Tuple          # AttnCache: every cross-attn site's cached output
    ms: Any               # multistep scheduler state (None for DDIM)
    state: Tuple          # frozen phase-1 StoreState (LocalBlend source)


def carry_spec(carry: PhaseCarry) -> str:
    """The pinned treedef of a hand-off carry: pytree structure plus every
    leaf's shape/dtype. Two carries with equal specs are exchangeable
    inputs of the same phase-2 program; the hand-off path hard-errors on a
    mismatch instead of letting XLA fail (or worse, retrace) later."""
    leaves, treedef = jax.tree_util.tree_flatten(carry)
    leaf_sig = ",".join(f"{tuple(x.shape)}/{x.dtype}" for x in leaves)
    return f"{treedef}|{leaf_sig}"


def phase2_controller(controller: Optional[Controller]
                      ) -> Optional[Controller]:
    """The slice of a controller the phase-2 program actually consumes.

    Past the gate the U-Net runs with ``controller=None`` (attention hooks
    are structurally gone); only the latent-space step callback survives —
    SpatialReplace injection and LocalBlend compositing against the frozen
    phase-1 store. Attention-edit parameters and the store flag are
    dropped, so e.g. a ``replace`` and a ``refine`` edit reduce to the SAME
    phase-2 controller (``None``) and their phase-2 lanes can share one
    compiled pool program — the serve layer's phase-2 compile key is
    derived from this reduction. For controllers the reduction maps to
    ``None`` the emitted ops are identical to passing the full controller
    (both step-callback branches are static no-ops), which is what keeps
    the pooled program bitwise-equal to the monolithic gated scan."""
    if controller is None:
        return None
    if controller.blend is None and controller.spatial_stop_inject is None:
        return None
    return controller.replace(edit=None, store=False)


def _store_state(layout: AttnLayout, b: int,
                 controller: Optional[Controller]) -> StoreState:
    """The controller's zeroed attention store for ``b`` conditional rows
    (f32 whatever the sampling dtype: it accumulates over the steps), ()
    where it keeps none or ``layout`` has no slot because nobody reads one
    (``AttnLayout.for_readers``). Its bytes are noted for the launch being
    traced (``Launch.store_bytes``): for a caller that takes the store back
    at a 96² latent the bound scales to 48² and five self sites hold
    (B, 10, 2304, 2304) each; for LocalBlend alone it is the blend's cross
    maps."""
    if controller is None or not controller.needs_store:
        return ()
    state = init_store_state(layout, b, dtype=jnp.float32)
    launches.note_store_bytes(sum(s.size * s.dtype.itemsize for s in state))
    return state


def _make_ms_step(schedule: sched_mod.DiffusionSchedule, scheduler_kind: str):
    use_plms = scheduler_kind == "plms"
    use_dpm = scheduler_kind == "dpm"

    def ms_step(ms, eps, t, latents):
        if use_plms:
            return sched_mod.plms_step(schedule, ms, eps, t, latents)
        if use_dpm:
            return sched_mod.dpm_step(schedule, ms, eps, t, latents)
        return ms, sched_mod.ddim_step(schedule, eps, t, latents)

    return ms_step


def _schedule_of(gate: Optional[int], reuse, layout: AttnLayout,
                 num_scan: int):
    """The one description of a run under the jitted entry points: an
    ``engine.reuse.ReuseSchedule``. ``gate=g`` is the uniform table, no gate
    the table with ``cfg_gate == steps`` and nothing cached."""
    if reuse is None:
        return reuse_mod.ReuseSchedule.uniform(
            num_scan if gate is None else gate, num_scan, layout)
    assert gate is None or gate == reuse.cfg_gate, (gate, reuse.cfg_gate)
    assert reuse.steps == num_scan, (reuse.steps, num_scan)
    return reuse


def _make_cfg_body(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context: jax.Array,
    b: int,
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    uncond_per_step: Optional[jax.Array],
    emit: bool,
    progress: bool,
    sp: Optional["SpConfig"],
    *,
    site_plan: Tuple[str, ...],
    held: Optional[Tuple],
    kernels=None,
):
    """The CFG step as the scan body of one reuse-schedule SEGMENT
    (engine.reuse): the batch-doubled U-Net under the segment's constant
    per-site ``site_plan`` with full controller hooks at computed sites,
    guidance, the solver, the controller's step callback.

    The carry is ``(latents, state, ms, cache, resid)``, and holds only
    what the plan makes it hold: ``held`` is the cache the body closes over
    where no site of the segment stores (the carry's slot is then ``()``),
    None where the carry has it; ``resid`` is None in the carry where no
    phase 2 follows to read the guidance residual."""
    ms_step = _make_ms_step(schedule, scheduler_kind)
    # What of the conditioning does not depend on the step, once ahead of
    # the scan (a conditioning with nothing of the kind comes back as it is).
    context = prepare(unet_params, cfg.unet, context)

    def body(carry, scan_in):
        latents, state, ms, cache, resid = carry
        step, t = scan_in
        progress_mod.emit_step(emit, step, phase="phase1", report=progress)
        ctx = context
        with jax.named_scope("sampler/cfg"):
            if uncond_per_step is not None:
                # Null-text: substitute this step's optimized uncond
                # embedding (the hidden states; a pooled vector stays as
                # encoded). Cast to the sampling dtype — the artifact stores
                # f32 (the optimizer's dtype), and a f32 leak here would
                # silently promote the whole CFG context (and the U-Net
                # matmuls) on the bf16 path.
                u = jax.lax.dynamic_index_in_dim(uncond_per_step, step, 0,
                                                 keepdims=False)
                states = context_of(context)
                ctx = with_context(context, jnp.concatenate(
                    [jnp.broadcast_to(u.astype(states.dtype),
                                      states[:b].shape),
                     states[b:]], axis=0))
            latent_in = jnp.concatenate([latents] * 2, axis=0)
        eps, state, out_cache = denoise(
            unet_params, cfg.unet, latent_in, t, ctx,
            layout=layout, controller=controller, state=state, step=step,
            sp=sp, attn_cache=cache if held is None else held,
            site_plan=site_plan, kernels=kernels)
        with jax.named_scope("sampler/cfg"):
            eps_uncond, eps_text = eps[:b], eps[b:]
            diff = eps_text - eps_uncond
            eps = eps_uncond + guidance_scale * diff
        with jax.named_scope("sampler/scheduler_step"):
            # v-prediction models (SD-2.1 768-v): convert to ε once per step.
            # Affine in the model output with the same x_t on both branches,
            # so combining CFG first is equivalent; the residual above is
            # taken before it, in the network's output space (see
            # ``_make_cond_body``).
            eps = sched_mod.to_epsilon(schedule, eps, t, latents)
            ms, latents = ms_step(ms, eps, t, latents)
        with jax.named_scope("sampler/controller_step"):
            latents = apply_step_callback(controller, layout, state, latents,
                                          step)
        return (latents, state, ms, out_cache if held is None else (),
                None if resid is None else diff), None

    return body


def _make_cond_body(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context_cond: jax.Array,
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    emit: bool,
    progress: bool,
    sp: Optional["SpConfig"],
    *,
    site_plan: Tuple[str, ...],
    held: Optional[Tuple],
    resid: jax.Array,
    state: Tuple,
    kernels=None,
):
    """The single-branch step past the CFG boundary as the scan body of one
    segment: the U-Net on the conditional half alone with no controller
    (attention hooks are structurally gone), guidance as a fixed
    extrapolation off the frozen hand-off ``resid``, then the CFG step's
    tail against the frozen phase-1 store ``state``. The carry is
    ``(latents, ms, cache)``; ``held`` as in :func:`_make_cfg_body`: sites
    that flip to reuse inside phase 2 keep storing until their step, and a
    segment with none closes over the cache."""
    ms_step = _make_ms_step(schedule, scheduler_kind)
    context_cond = prepare(unet_params, cfg.unet, context_cond)

    def body(carry, scan_in):
        latents, ms, cache = carry
        step, t = scan_in
        progress_mod.emit_step(emit, step, phase="phase2", report=progress)
        eps_text, _, out_cache = denoise(
            unet_params, cfg.unet, latents, t, context_cond,
            layout=layout, controller=None, state=(), step=step, sp=sp,
            attn_cache=cache if held is None else held,
            site_plan=site_plan, kernels=kernels)
        # SD-Acc-style fixed extrapolation: CFG's uncond branch is gone;
        # ε = ε_text + (g−1)·(ε_text − ε_uncond)|_gate reuses the captured
        # last-phase-1 residual as the guidance direction. The residual lives
        # in the network's OUTPUT space (v for a v-prediction model, as
        # phase 1 captured it ahead of ``to_epsilon``): that is the space
        # guidance is applied in at every full step, so this line is the full
        # step's formula with the uncond branch frozen, and one conversion
        # below serves both. Held in ε-space the same residual would weigh
        # α_gate/α_t more at step t (ε_c − ε_u = α_t·(v_c − v_u)): another
        # approximation, not a more exact one; the plain reference
        # (benchmarks/reference/latent_diffusion_v.py) pins this one.
        with jax.named_scope("sampler/cfg"):
            eps = eps_text + (guidance_scale - 1.0) * resid
        with jax.named_scope("sampler/scheduler_step"):
            eps = sched_mod.to_epsilon(schedule, eps, t, latents)
            ms, latents = ms_step(ms, eps, t, latents)
        # Latent-space controller effects (LocalBlend compositing /
        # SpatialReplace injection) continue against the frozen phase-1
        # store.
        with jax.named_scope("sampler/controller_step"):
            latents = apply_step_callback(controller, layout, state, latents,
                                          step)
        return (latents, ms, out_cache if held is None else ()), None

    return body


def _phase1_scan(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context: jax.Array,            # (2B, L, D) [uncond; cond]
    latents: jax.Array,            # (B, h, w, c)
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    *,
    reuse,                         # engine.reuse.ReuseSchedule (static)
    uncond_per_step: Optional[jax.Array] = None,
    progress: bool = False,
    metrics: bool = False,
    sp: Optional["SpConfig"] = None,
    kernels=None,                  # kernels.KernelConfig (static)
) -> PhaseCarry:
    """The phase-1 executor: steps ``[0, cfg_gate)`` under full CFG and the
    controller's hooks, cut into constant-plan segments
    (engine.reuse.segments), one ``lax.scan`` each. A site stores its output
    every step until the step it flips to its cache, so the cache holds the
    last stored step's values. Returns the :class:`PhaseCarry` a phase-2
    program continues from, full-batch cache leaves sliced to the
    conditional half.

    What the carry holds follows from the table ``reuse``
    (:func:`_schedule_of`). For ``gate=g``: one segment whose cross sites
    store, the cache and the guidance residual in the carry. With no gate:
    one segment, nothing cached, and no residual because no phase 2 reads
    it — the carry is ``(latents, store state, solver state)``."""
    num_scan = schedule.timesteps.shape[0]
    sched1 = reuse_mod.phase1_view(reuse)
    emit = progress or metrics
    b = latents.shape[0]
    state = _store_state(layout, b, controller)
    # Multistep-solver state carried through the scan (PLMS ring buffer or
    # DPM x0 history; None for single-step DDIM), handed across the phase
    # boundary.
    ms_state = sched_mod.init_multistep_state(scheduler_kind, latents.shape,
                                              latents.dtype)
    steps = jnp.arange(num_scan, dtype=jnp.int32)
    cache = reuse_mod.init_schedule_cache(layout, sched1, b, phase=1,
                                          dtype=latents.dtype)
    resid = jnp.zeros_like(latents) if sched1.gated else None
    for seg in reuse_mod.segments(layout, sched1, phase=1):
        body = _make_cfg_body(unet_params, cfg, layout, schedule,
                              scheduler_kind, context, b, controller,
                              guidance_scale, uncond_per_step, emit,
                              progress, sp, site_plan=seg.plan,
                              held=None if seg.stores else cache,
                              kernels=kernels)
        (latents, state, ms_state, kept, resid), _ = jax.lax.scan(
            body,
            (latents, state, ms_state, cache if seg.stores else (), resid),
            (steps[seg.start:seg.stop],
             schedule.timesteps[seg.start:seg.stop]))
        if seg.stores:
            cache = kept
    cache = reuse_mod.slice_cache_to_cond(layout, sched1, cache, b)
    return PhaseCarry(latents=latents, resid=resid, cache=cache,
                      ms=ms_state, state=state)


def _phase2_scan(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context_cond: jax.Array,       # (B, L, D) — the uncond half is GONE
    carry: PhaseCarry,
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    *,
    reuse,                         # engine.reuse.ReuseSchedule (static)
    progress: bool = False,
    metrics: bool = False,
    sp: Optional["SpConfig"] = None,
    kernels=None,                  # kernels.KernelConfig (static)
) -> jax.Array:
    """The phase-2 executor: steps ``[cfg_gate, S)`` off a
    :class:`PhaseCarry`: single-branch U-Net (no uncond batch half),
    guidance as a fixed extrapolation off the captured residual (SD-Acc),
    cached sites served from the cache (TAD), segmented so a site may keep
    computing past the CFG boundary and flip to reuse at its own step.
    ``gate=g`` is one segment with every cross site in ``use`` and the
    cache closed over. ``controller`` here is the phase-2 slice
    (:func:`phase2_controller` for pooled serving; the monolithic path
    passes the full controller — both emit identical ops)."""
    num_scan = schedule.timesteps.shape[0]
    sched2 = reuse_mod.phase2_view(reuse)
    emit = progress or metrics
    steps = jnp.arange(num_scan, dtype=jnp.int32)
    latents, ms_state, cache = carry.latents, carry.ms, carry.cache
    for seg in reuse_mod.segments(layout, sched2, phase=2):
        body = _make_cond_body(unet_params, cfg, layout, schedule,
                               scheduler_kind, context_cond, controller,
                               guidance_scale, emit, progress, sp,
                               site_plan=seg.plan,
                               held=None if seg.stores else cache,
                               resid=carry.resid, state=carry.state,
                               kernels=kernels)
        (latents, ms_state, kept), _ = jax.lax.scan(
            body, (latents, ms_state, cache if seg.stores else ()),
            (steps[seg.start:seg.stop],
             schedule.timesteps[seg.start:seg.stop]))
        if seg.stores:
            cache = kept
    return latents


def _denoise_scan(
    unet_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context: jax.Array,            # (2B, L, D) [uncond; cond]
    latents: jax.Array,            # (B, h, w, c)
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    uncond_per_step: Optional[jax.Array] = None,  # (T, 1, L, D) null-text embeddings
    progress: bool = False,
    sp: Optional["SpConfig"] = None,
    gate: Optional[int] = None,    # static: first phase-2 scan step; None/S = off
    metrics: bool = False,         # static: trace the telemetry callback in
    reuse=None,                    # engine.reuse.ReuseSchedule (static)
    kernels=None,                  # kernels.KernelConfig (static)
) -> Tuple[jax.Array, StoreState]:
    """Scan over timesteps. Returns (final latents, final store state).

    Phase 1, then phase 2 if the run's table (:func:`_schedule_of`) drops
    the CFG branch before the end (TAD arXiv 2404.02747 + SD-Acc arXiv
    2507.01309, mapped onto P2P's explicit step windows):

    - phase 1 (steps ``0..cfg_gate``): the batch-doubled CFG U-Net with full
      controller hooks, storing the output of every site that will be
      served from its cache and the CFG residual ``ε_text − ε_uncond`` (each
      overwritten per step, so the final carry holds the last values);
    - phase 2 (steps ``cfg_gate..S``): a single-branch U-Net — no uncond
      half, guidance folded into a fixed extrapolation off the captured
      residual, cached sites replaced by their cached outputs. The
      controller is dropped at the U-Net level (edit windows end before the
      gate under ``gate='auto'``); its latent-space step callback
      (LocalBlend / SpatialReplace) still runs against the frozen phase-1
      store.

    These are the two programs the serve layer's disaggregated pools
    compile separately, composed into one — op for op the split execution,
    which is what makes a pooled hand-off bitwise-equal to a single-program
    gated run. ``gate=None`` (or ``gate == S``) is one scan with no cache
    buffers and no residual carry.

    ``metrics`` traces the per-step host callback in even when ``progress``
    is off (phase-tagged, so ``obs.device.StepCollector`` can histogram
    phase-1 vs phase-2 ms/step); with both off the program carries no
    callback at all — the telemetry-disabled jaxpr-identity contract.
    """
    b = latents.shape[0]
    sched = _schedule_of(gate, reuse, layout, schedule.timesteps.shape[0])
    if uncond_per_step is not None and (
            sched.gated or reuse_mod.cached_sites(layout, sched)):
        raise ValueError("phase-gated sampling and reuse schedules cannot "
                         "run under per-step null-text uncond embeddings "
                         "(validated upstream)")
    carry = _phase1_scan(unet_params, cfg, layout, schedule, scheduler_kind,
                         context, latents, controller, guidance_scale,
                         uncond_per_step=uncond_per_step, progress=progress,
                         metrics=metrics, sp=sp, reuse=sched, kernels=kernels)
    if not sched.gated:
        # CFG never drops: the whole scan ran in phase 1 (cached sites
        # still saved their compute).
        return carry.latents, carry.state
    # Slice the conditional context half once, outside the phase-2 body: a
    # slice inside the scan would pull the full [uncond; cond] tensor into
    # the body as a constant — the uncond half must not even be an input.
    latents = _phase2_scan(unet_params, cfg, layout, schedule,
                           scheduler_kind,
                           jax.tree.map(lambda c: c[b:], context), carry,
                           controller,
                           guidance_scale, progress=progress,
                           metrics=metrics, sp=sp, reuse=sched,
                           kernels=kernels)
    return latents, carry.state


@partial(jax.jit, static_argnames=("cfg", "layout", "scheduler_kind",
                                   "return_store", "progress", "sp", "gate",
                                   "metrics", "reuse", "kernels"))
def _text2image_jit(
    unet_params: Any,
    vae_params: Any,
    cfg: PipelineConfig,
    layout: AttnLayout,
    schedule: sched_mod.DiffusionSchedule,
    scheduler_kind: str,
    context_cond: jax.Array,
    context_uncond: jax.Array,
    latents: jax.Array,
    controller: Optional[Controller],
    guidance_scale: jax.Array,
    uncond_per_step: Optional[jax.Array],
    return_store: bool,
    progress: bool = False,
    sp: Optional["SpConfig"] = None,
    gate: Optional[int] = None,
    metrics: bool = False,
    reuse=None,
    kernels=None,
):
    context = cfg_rows(context_uncond, context_cond)
    latents, state = _denoise_scan(
        unet_params, cfg, layout, schedule, scheduler_kind, context, latents,
        controller, guidance_scale, uncond_per_step, progress=progress, sp=sp,
        gate=gate, metrics=metrics, reuse=reuse, kernels=kernels)
    image = vae_mod.decode(vae_params, cfg.vae, latents.astype(jnp.float32))
    image = vae_mod.to_uint8(image)
    return (image, latents, state) if return_store else (image, latents, ())


def text2image(
    pipe: Pipeline,
    prompts,
    controller: Optional[Controller] = None,
    *,
    num_steps: Optional[int] = None,
    guidance_scale: Optional[float] = None,
    scheduler: Optional[str] = None,
    latent: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    uncond_embeddings: Optional[jax.Array] = None,
    negative_prompt: Optional[str] = None,
    layout: Optional[AttnLayout] = None,
    dtype=jnp.float32,
    return_store: bool = False,
    progress: bool = False,
    sp: Optional["SpConfig"] = None,
    gate=None,
    metrics: bool = False,
    schedule=None,
    kernels=None,
):
    """Generate an edit group of images from prompts under attention control —
    the `/root/reference/ptp_utils.py:129-172` entry point.

    ``uncond_embeddings``: optional (T, 1, L, D) per-step null-text
    embeddings; otherwise the encoded unconditional prompt is broadcast over
    all steps. ``negative_prompt`` replaces the default ``""`` unconditional
    text (classifier-free guidance then steers *away* from it — a diffusers
    capability the reference lacks); mutually exclusive with
    ``uncond_embeddings``. ``sp`` (a :class:`p2p_tpu.models.unet.SpConfig`)
    shards the pixel axis of large untouched self-attention sites over a
    mesh axis with ring attention — the long-context scaling axis (image
    resolution; SURVEY §5) the reference lacks entirely.

    ``gate`` enables phase-gated sampling (see :func:`resolve_gate`): steps
    past the gate run a single-branch U-Net (no CFG uncond half) with every
    cross-attention site served from the cached last-phase-1-step output —
    the per-step cost drops roughly in half past the gate at a small,
    bounded drift (PERF.md "Beyond the XLA ceiling"). ``gate=None`` (or the
    full step count) is bitwise-identical to ungated sampling. Incompatible
    with ``uncond_embeddings``: the null-text artifact optimizes the uncond
    branch at *every* step, so truncating it would silently misalign the
    replay — rejected with an error instead. Returns
    ``(images uint8 (B,H,W,3), x_T, store)``.

    ``schedule`` (mutually exclusive with ``gate``) is a per-site per-step
    reuse schedule — a spec dict (``engine.reuse.validate_spec``; the CLI
    loads ``--schedule FILE`` artifacts like
    ``tools/schedules/default_v1.json``) or an already-resolved
    ``engine.reuse.ReuseSchedule``. Each attention site flips from
    computing to serving its cached cross-attention output (TAD) or
    inherited self-attention feature (A-SDM) at its own step;
    ``cfg_gate`` plays the gate's role for the CFG branch. The uniform
    table is the ``gate=g`` program.

    ``kernels`` (a static :class:`p2p_tpu.kernels.KernelConfig`) routes
    covered controller-edited attention sites to the fused-edit Pallas
    kernel — the prompt-to-prompt edit applied inside the attention tile, so
    the ``(2B·heads, P, K)`` probability tensor never reaches HBM (PERF.md
    "In-kernel editing"). It is a pure lowering choice threaded through the
    jit static args: each distinct config is one compiled program, composing
    with ``gate``/``schedule`` segment lowering (``use`` segments skip
    attention entirely; attention-store sites keep the materialized path).
    ``kernels=None`` compiles the exact pre-existing program.

    ``metrics`` enables device-side telemetry (docs/OBSERVABILITY.md):
    phase-tagged step callbacks are traced into the program and the resolved
    gate step / scan length / CFG batch land in the default registry as
    gauges. Numerics-neutral — callbacks are pure side channel — and with
    ``metrics=False`` (and ``progress=False``) the compiled program is
    identical to one built before this flag existed. Callers that want the
    step stream collected must install the host sink
    (``obs.device.instrument``); the CLI ``--metrics`` flag does.
    """
    with span("entry.text2image"):
        if negative_prompt and uncond_embeddings is not None:
            raise ValueError("negative_prompt and uncond_embeddings are mutually "
                             "exclusive (null-text already optimized the uncond)")
        cfg = pipe.config
        num_steps = num_steps or cfg.num_steps
        scheduler = scheduler or cfg.scheduler.kind
        if uncond_embeddings is not None:
            require_unet(cfg.unet, "uncond_embeddings (null-text inversion)")
            if scheduler != "ddim":
                # PLMS scans T+1 steps (warm-up double-evaluation); per-step
                # null-text embeddings are optimized against the DDIM trajectory
                # and would silently misalign (`/root/reference/null_text.py:23`
                # — the null-text path is DDIM-only).
                raise ValueError("uncond_embeddings require scheduler='ddim'")
            if uncond_embeddings.shape[0] != num_steps:
                raise ValueError(
                    f"uncond_embeddings has {uncond_embeddings.shape[0]} steps, "
                    f"sampling uses {num_steps}")
        with span("entry.prepare", steps=int(num_steps), batch=len(prompts)):
            gs = jnp.asarray(cfg.guidance_scale if guidance_scale is None else guidance_scale,
                             dtype=jnp.float32)
            if layout is None:
                from ..models.config import denoiser_layout
                layout = denoiser_layout(cfg.unet)
            controller = layout.resolve(controller)
            layout = layout.for_readers(controller, return_store)
            if rng is None:
                rng = jax.random.PRNGKey(0)

            tsched = sched_mod.schedule_from_config(num_steps, cfg.scheduler,
                                                    kind=scheduler)
            num_scan = tsched.timesteps.shape[0]
            gate_step, reuse_sched = resolve_reuse(gate, schedule, layout, num_scan,
                                                   controller)
            x_t, latents = init_latent(latent, pipe.latent_shape, rng, len(prompts),
                                       dtype)
        if gate_step < num_scan and uncond_embeddings is not None:
            # The null-text window spans every step (validated (T,1,L,D)
            # above): any gate < T truncates inside it. Reject loudly — a
            # silently misaligned replay looks plausible and is wrong.
            raise ValueError(
                f"gate={gate!r} (step {gate_step}) conflicts with per-step "
                f"null-text uncond_embeddings, which are active through all "
                f"{num_scan} steps: CFG truncation would drop the optimized "
                "uncond branch mid-window. Run null-text replays with "
                "gate=None.")
        if reuse_sched is not None and uncond_embeddings is not None:
            # A non-uniform schedule reroutes per-site features even when its
            # cfg_gate keeps CFG alive: the per-step optimized uncond would
            # replay against a different trajectory — same loud rejection.
            raise ValueError(
                "schedule conflicts with per-step null-text "
                "uncond_embeddings: cached/inherited sites change the "
                "trajectory the uncond branch was optimized against. Run "
                "null-text replays with schedule=None.")
        if reuse_sched is None:
            warn_gate_truncation(gate_step, num_scan, controller)
        context_cond = encode_prompts(pipe, prompts, dtype=dtype)
        context_uncond = encode_prompts(
            pipe, [negative_prompt or ""] * len(prompts), dtype=dtype)

        if progress:
            progress_mod.activate(tsched.timesteps.shape[0])
        if metrics:
            # Host-side run descriptors for the snapshot: the gate decomposition
            # (per-phase ms/step arrives via the step callbacks) plus the CFG
            # batch shape phase 1 actually runs.
            from ..obs import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.gauge("sampler_gate_step",
                      "first phase-2 scan step (== scan length: ungated)"
                      ).set(float(gate_step))
            reg.gauge("sampler_scan_steps", "scan length").set(float(num_scan))
            reg.gauge("sampler_cfg_batch",
                      "CFG-doubled U-Net batch in phase 1 (2B)"
                      ).set(float(2 * len(prompts)))

        with span("sampler.text2image", steps=int(num_scan), gate=int(gate_step),
                  batch=len(prompts)):
            # Span covers trace/compile + async dispatch (execution completes
            # when the caller materializes the arrays) — it marks the host
            # region for Perfetto alignment, not device wall time.
            args = (pipe.unet_params, pipe.vae_params, cfg, layout, tsched,
                    scheduler, context_cond, context_uncond, latents,
                    controller, gs, uncond_embeddings, return_store)
            kwargs = dict(progress=progress, sp=sp, gate=gate_step,
                          metrics=metrics, reuse=reuse_sched, kernels=kernels)
            mark = launches.built()
            keys = live_keys(context_uncond)
            if keys is not None:
                launches.note_caption_keys(keys + live_keys(context_cond),
                                           cfg.unet.context_len)
            image, latents_out, state = _text2image_jit(*args, **kwargs)
            launches.keep_if_built(mark, _text2image_jit, args, kwargs)
        return image, x_t, state
