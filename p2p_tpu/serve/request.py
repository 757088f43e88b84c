"""Request schema + admission-time validation for the serving layer.

A :class:`Request` is the JSONL unit of work the serve loop consumes: one
generation (``prompt``) or one prompt-to-prompt edit (``prompt`` +
``target``), with the same knobs the CLI exposes per run (mode, windows,
equalizer, seed, steps, scheduler, gate, negative prompt) plus the
request-level fields the one-shot CLI has no use for: arrival time, a
deadline, a priority, and a stable ``request_id``.

Validation happens at admission, not dispatch: a request that can never run
(bad mode/scheduler, a gate spec ``engine.sampler.resolve_gate`` rejects, a
controller the factory can't build) is rejected with a reason before it
costs queue capacity — the same controller factory and gate checks the CLI
path uses (``cli.controller_from_opts`` / ``resolve_gate``), so the serve
surface can never accept a spec the direct surface would refuse.

:func:`prepare` also derives the keys the batcher runs on:

- ``compile_key`` — everything that changes the XLA program: steps,
  scheduler kind, resolved gate step, group batch (1 or 2 prompts), and the
  controller's *structure* (pytree treedef + leaf shapes/dtypes — edit
  values are traced leaves and deliberately absent).
- ``batch_key`` — ``compile_key`` plus the values that are traced but
  *shared* across a sweep call (guidance scale): requests may share a
  compiled program yet not a batch.

- ``content_key`` — the *semantic cache* address (ISSUE 13): every field
  that determines the request's **output images** — prompts, edit values,
  seed, steps, scheduler, guidance, negative prompt, resolved gate step —
  and nothing that doesn't (``request_id``, arrival/deadline, priority,
  tenant, tier are pure scheduling metadata). Two requests sharing a
  content key produce bitwise-identical images, so one may be served the
  other's result; a field missing from the key would serve *wrong* images
  (cache poisoning), a superfluous one would split identical traffic
  (lost hits). The ``OUTPUT_DETERMINING`` sweep in
  ``analysis.compile_key`` guards both directions per field, the same
  completeness idiom that covers ``compile_key``.

Gated requests (resolved gate step < scan length) additionally carry the
**per-phase** keys of the disaggregated program pools:

- ``phase1_key`` — the phase-1 pool program (full CFG + controller hooks,
  steps ``[0, gate)``, returns the hand-off carry): the monolithic
  compile key behind a ``"phase1"`` tag — every component shapes phase 1.
- ``phase2_key`` — the phase-2 pool program (single-branch U-Net off the
  carry, steps ``[gate, S)`` + decode): the controller component is the
  *phase-2 slice* (``engine.sampler.phase2_controller``) — attention-edit
  structure is gone past the gate, so e.g. ``replace`` and ``refine``
  edits share ONE phase-2 program and their lanes pack together.
- ``phase2_batch_key`` — ``phase2_key`` + guidance, the phase-2 pool's
  batching key: lanes from *different requests* (different phase-1
  batches, even different edit modes) co-batch here.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Tuple

from . import scheduling

_SCHEDULERS = ("ddim", "plms", "dpm")
_MODES = ("replace", "refine")

#: The partition of Request fields by OUTPUT identity (ISSUE 13).
#: ``CONTENT_FIELDS`` determine the images a request produces and feed the
#: semantic-cache ``content_key``; ``SCHEDULING_FIELDS`` never do (they
#: decide *when/whether* a request runs, not *what* it computes). The two
#: tuples must cover the schema exactly — ``content_key`` errors on a
#: field in neither, so extending the schema forces a cache-identity
#: decision (the compile-key completeness discipline).
CONTENT_FIELDS = ("prompt", "target", "mode", "cross_steps", "self_steps",
                  "blend_words", "equalizer", "blend_resolution", "seed",
                  "steps", "scheduler", "guidance", "negative_prompt",
                  "gate", "schedule")
SCHEDULING_FIELDS = ("request_id", "arrival_ms", "deadline_ms", "priority",
                     "tenant", "tier")


@dataclasses.dataclass(frozen=True)
class Request:
    """One unit of serving work. ``target=None`` is pure generation; a
    ``target`` makes it a 2-prompt edit group (source lane + edited lane,
    the CLI ``edit`` semantics)."""

    request_id: str
    prompt: str
    target: Optional[str] = None
    mode: str = "refine"
    cross_steps: float = 0.8
    self_steps: float = 0.4
    blend_words: Optional[str] = None
    equalizer: Optional[str] = None
    blend_resolution: Optional[int] = None   # None: the model's own level
    seed: int = 8191
    steps: int = 50
    scheduler: str = "ddim"
    guidance: float = 7.5
    negative_prompt: Optional[str] = None
    gate: Any = None            # None | 'auto' | float fraction | int step
    # Per-site per-step reuse schedule (ISSUE 15): a JSON spec object
    # (engine.reuse.validate_spec), the generalized gate — mutually
    # exclusive with ``gate``. The RESOLVED static table joins the
    # compile/content keys (identical tables from different files pool;
    # a one-cell difference splits); the uniform table normalizes onto
    # the plain gate path and pools with gate=g traffic.
    schedule: Any = None
    arrival_ms: float = 0.0     # virtual trace time (loadgen / replay)
    deadline_ms: Optional[float] = None  # relative to arrival; None = none
    priority: int = 0           # higher dispatches first (within a tier)
    # SLO scheduling metadata (serve.scheduling): who the request belongs
    # to and what latency class it bought. Pure scheduler inputs — they
    # never join a compile key (tiers must not fragment programs) and,
    # absent, the whole SLO layer is byte-invisible (to_dict drops None).
    tenant: Optional[str] = None   # quota/fair-share identity
    tier: Optional[str] = None     # one of scheduling.TIERS

    @property
    def prompts(self) -> Tuple[str, ...]:
        return (self.prompt,) if self.target is None else (self.prompt,
                                                           self.target)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return {k: v for k, v in d.items() if v is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "Request":
        """Build a Request from a JSONL record, rejecting unknown keys (the
        honored-flags discipline: a typo'd field must error, not silently
        do nothing)."""
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(f"unknown request field(s) {sorted(unknown)}; "
                             f"valid: {sorted(fields)}")
        if "request_id" not in d or "prompt" not in d:
            raise ValueError("request needs 'request_id' and 'prompt'")
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Cancel:
    """Control record: cancel a previously submitted request by id (only
    guaranteed before its batch dispatches)."""

    request_id: str


def parse_jsonl_line(line: str):
    """One serve-input line → :class:`Request` or :class:`Cancel` (a line of
    the form ``{"cancel": "<id>"}``), or ``None`` for a blank line."""
    line = line.strip()
    if not line:
        return None
    d = json.loads(line)
    if not isinstance(d, dict):
        raise ValueError(f"request line must be a JSON object, got {d!r}")
    if set(d) == {"cancel"}:
        return Cancel(request_id=str(d["cancel"]))
    return Request.from_dict(d)


def _structural_validate(req: Request) -> None:
    if not req.request_id:
        raise ValueError("empty request_id")
    if not req.prompt:
        raise ValueError("empty prompt")
    if req.steps < 1:
        raise ValueError(f"steps must be >= 1, got {req.steps}")
    if req.scheduler not in _SCHEDULERS:
        raise ValueError(f"unknown scheduler {req.scheduler!r}; "
                         f"valid: {', '.join(_SCHEDULERS)}")
    if req.mode not in _MODES:
        raise ValueError(f"unknown mode {req.mode!r}; valid: "
                         f"{', '.join(_MODES)}")
    if req.target is None and (req.blend_words or req.equalizer):
        raise ValueError("blend_words/equalizer need a 'target' edit prompt")
    if req.deadline_ms is not None and req.deadline_ms <= 0:
        raise ValueError(f"deadline_ms must be positive, got {req.deadline_ms}")
    if isinstance(req.gate, str) and req.gate != "auto":
        raise ValueError(f"gate must be null, 'auto', a fraction or a step "
                         f"index, got {req.gate!r}")
    if req.schedule is not None:
        if req.gate is not None:
            raise ValueError("gate and schedule are mutually exclusive: a "
                             "reuse schedule generalizes the gate")
        from ..engine.reuse import validate_spec

        # Structural (layout-free) validation at admission — resolution
        # against the model's site layout happens in prepare().
        validate_spec(req.schedule)
    # Scheduling metadata is validated HERE, at admission, so a bad value
    # is a clean schema reject — never a TypeError inside the queue's sort
    # comparator three stages later (bool is an int subclass and would
    # sort, but it is always a caller bug: rejected explicitly).
    if isinstance(req.priority, bool) or not isinstance(req.priority, int):
        raise ValueError(f"priority must be an int, "
                         f"got {type(req.priority).__name__} "
                         f"{req.priority!r}")
    if abs(req.priority) > scheduling.PRIORITY_BOUND:
        raise ValueError(f"priority must be within "
                         f"±{scheduling.PRIORITY_BOUND}, got {req.priority}")
    if req.tenant is not None:
        if not isinstance(req.tenant, str) or not req.tenant:
            raise ValueError(f"tenant must be a non-empty string, "
                             f"got {req.tenant!r}")
        if len(req.tenant) > scheduling.TENANT_MAX_LEN:
            raise ValueError(f"tenant id longer than "
                             f"{scheduling.TENANT_MAX_LEN} chars")
    if req.tier is not None and req.tier not in scheduling.TIERS:
        raise ValueError(f"unknown tier {req.tier!r}; valid: "
                         f"{', '.join(scheduling.TIERS)}")


def controller_signature(controller) -> Tuple:
    """The controller's *static* program identity: pytree structure + leaf
    shapes/dtypes. Edit values (equalizer scales, window schedules,
    thresholds) are traced leaves and must NOT appear here — two requests
    whose controllers differ only in values share one compiled program."""
    if controller is None:
        return ("none",)
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(controller)
    return (str(treedef),
            tuple((tuple(x.shape), str(getattr(x, "dtype", type(x).__name__)))
                  for x in leaves))


def content_key(req: Request, gate_step: int, model_name: str,
                sched_key: Optional[Tuple] = None) -> Tuple:
    """The semantic-cache address: every output-determining field, nothing
    else (ISSUE 13). Keyed on the *resolved* gate step, not the raw spec —
    ``gate=0.5`` and ``gate=2`` at ``steps=4`` run the identical
    trajectory and must share one cache line. Edit knobs (mode, windows,
    blend, equalizer) only shape the output when a ``target`` builds a
    controller, so a pure generation normalizes them away — two
    generations differing only in an ignored ``mode`` are the same
    traffic. Errors if the schema grew a field outside the declared
    CONTENT/SCHEDULING partition: a new field must decide its cache
    identity before it can ride a cached serve."""
    declared = set(CONTENT_FIELDS) | set(SCHEDULING_FIELDS)
    fields = {f.name for f in dataclasses.fields(Request)}
    if fields != declared:
        raise ValueError(
            f"Request fields {sorted(fields ^ declared)} are missing from "
            "the CONTENT_FIELDS/SCHEDULING_FIELDS partition: decide "
            "whether they determine the output before caching can serve "
            "this schema")
    edit = (None if req.target is None else
            (req.target, req.mode, float(req.cross_steps),
             float(req.self_steps), req.blend_words, req.equalizer,
             None if req.blend_resolution is None
             else int(req.blend_resolution)))
    # ``sched_key`` is the RESOLVED reuse table (engine.reuse key form),
    # not the raw spec: specs that resolve identically (fraction vs step,
    # different files) share a cache line, and the uniform table (None
    # here) shares one with plain gate=g traffic.
    return ("content", model_name, req.prompt, edit, int(req.seed),
            int(req.steps), req.scheduler, float(req.guidance),
            req.negative_prompt, int(gate_step), sched_key)


@dataclasses.dataclass(frozen=True)
class PreparedRequest:
    """A validated request bound to a pipeline: controller built, gate
    resolved, batching keys derived (monolithic + per-phase pool keys),
    plus the semantic-cache ``content_key`` (always derived — a pure
    tuple — but only *read* when a ``SemCache`` is active)."""

    request: Request
    controller: Any
    gate_step: int
    scan_steps: int
    compile_key: Tuple
    batch_key: Tuple
    phase1_key: Optional[Tuple] = None      # None = ungated (single-pool)
    phase2_key: Optional[Tuple] = None
    phase2_batch_key: Optional[Tuple] = None
    content_key: Optional[Tuple] = None
    #: The resolved reuse table (engine.reuse.ReuseSchedule) — None when
    #: the request has no schedule or it normalized to the uniform gate.
    #: The hand-off carry template and the runners read the TABLE from
    #: here/the keys; the raw spec never leaves the Request.
    schedule: Any = None

    @property
    def gated(self) -> bool:
        """Does this request cross the phase gate (and therefore the
        hand-off) when served through the disaggregated pools?"""
        return self.gate_step < self.scan_steps


def prepare(req: Request, pipe) -> PreparedRequest:
    """Validate ``req`` against ``pipe`` and derive its batching keys.

    Raises ``ValueError`` with a human-readable reason on any spec the
    direct CLI path would also refuse — reusing the CLI's controller
    factory (``cli.controller_from_opts``) and the sampler's gate
    resolution/validation (``engine.sampler.resolve_gate``)."""
    _structural_validate(req)

    from ..cli import controller_from_opts
    from ..engine import reuse as reuse_mod
    from ..engine.sampler import resolve_reuse
    from ..models.config import denoiser_layout
    from ..models.denoiser import require_unet
    from ..ops import schedulers as sched_mod

    require_unet(pipe.config.unet, "the serve engine")
    layout = denoiser_layout(pipe.config.unet)
    controller = None
    if req.target is not None:
        # resolved here, at admission: a LocalBlend side the model keeps no
        # map of is the request's error, not a batch's trace failure
        controller = layout.resolve(controller_from_opts(
            list(req.prompts), pipe.tokenizer, req.steps,
            mode=req.mode, cross_steps=req.cross_steps,
            self_steps=req.self_steps, blend_words=req.blend_words,
            equalizer=req.equalizer, blend_resolution=req.blend_resolution))

    # Same scan length the sampler will run (PLMS warm-up adds one step).
    schedule = sched_mod.schedule_from_config(req.steps, pipe.config.scheduler,
                                              kind=req.scheduler)
    scan_steps = int(schedule.timesteps.shape[0])
    # ``resolve_reuse`` is the same gate/schedule resolution every sampling
    # surface uses: it rejects gate+schedule, resolves the spec against the
    # model's site layout, normalizes a UNIFORM table to the plain gate
    # (``reuse=None`` — pools with gate=g traffic) and fires the per-site
    # window-conflict warning for non-uniform tables.
    gate_step, reuse_sched = resolve_reuse(req.gate, req.schedule, layout,
                                           scan_steps, controller)
    sched_key = None if reuse_sched is None else reuse_sched.key()

    compile_key = (pipe.config.name, req.steps, req.scheduler, gate_step,
                   len(req.prompts), controller_signature(controller),
                   sched_key)
    batch_key = compile_key + (float(req.guidance),)
    phase1_key = phase2_key = phase2_batch_key = None
    if gate_step < scan_steps:
        from ..engine.sampler import phase2_controller

        # Phase 1 is shaped by everything the monolithic program is; phase 2
        # only by what survives the gate — the reduced controller slice.
        # Conservative components (steps AND gate) stay in both keys: the
        # compile-key completeness sweep (analysis.compile_key) guards both
        # directions per field, and a gate change that altered a phase
        # program without its key would be cache poisoning. The SCHEDULE
        # component is per-phase PROJECTED (engine.reuse.phase{1,2}_view):
        # a table cell that only moves a phase-1 flip must not split the
        # phase-2 pool — lanes from schedules differing only before the
        # boundary still pack into one phase-2 program.
        # A projection that collapses to the UNIFORM table is the plain
        # gate=g phase program — its key component normalizes to None so
        # e.g. a schedule whose only non-uniformity is a phase-1 flip
        # packs its phase-2 lanes with plain-gate traffic (the views
        # preserve the carry's leaf set, so the pooled program's hand-off
        # pytree matches structurally too).
        def view_key(view_fn):
            if reuse_sched is None:
                return None
            view = view_fn(reuse_sched)
            return None if view.uniform_gate is not None else view.key()

        key1 = view_key(reuse_mod.phase1_view)
        key2 = view_key(reuse_mod.phase2_view)
        phase1_key = ("phase1", pipe.config.name, req.steps, req.scheduler,
                      gate_step, len(req.prompts),
                      controller_signature(controller), key1)
        phase2_key = ("phase2", pipe.config.name, req.steps, req.scheduler,
                      gate_step, len(req.prompts),
                      controller_signature(phase2_controller(controller)),
                      key2)
        phase2_batch_key = phase2_key + (float(req.guidance),)
    return PreparedRequest(request=req, controller=controller,
                           gate_step=gate_step, scan_steps=scan_steps,
                           compile_key=compile_key, batch_key=batch_key,
                           phase1_key=phase1_key, phase2_key=phase2_key,
                           phase2_batch_key=phase2_batch_key,
                           content_key=content_key(req, gate_step,
                                                   pipe.config.name,
                                                   sched_key),
                           schedule=reuse_sched)
