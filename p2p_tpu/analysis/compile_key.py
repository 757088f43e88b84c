"""Compile-key completeness — the ``ProgramCache`` poisoning/churn guard.

``serve.request.prepare`` derives ``compile_key`` by hand: the fields that
change the XLA program must be in it (two requests sharing a key MUST mean
the same program — a missing field silently *poisons* the cache: request B
runs request A's program), and fields that don't change the program must be
absent (a superfluous field splits one program across many keys — retracing
churn, and the dynamic batcher can then never co-batch the two requests).

This checker stops trusting the hand-derivation: it sweeps **every**
``Request`` field, perturbs it against a base request, traces the serve
batch program each variant would compile (``jax.make_jaxpr`` — structural
tracing only, no XLA), and asserts both directions per field:

- program changed  ⟹  ``compile_key`` changed   (else: cache poisoning)
- program unchanged ⟹ ``compile_key`` unchanged (else: retracing churn)

The sweep also fails on any ``Request`` field it has no variant for — a
*new* field added to the schema cannot dodge the checker by omission.

The program fingerprint is the jaxpr's printed structure: op sequence,
shapes, dtypes, scan lengths, sub-jaxprs. Constant *values* (e.g. a
scheduler's sigma table) don't print — a field that changed only trained
constants of identical shape would be invisible — but every field that can
change the program today does it structurally (steps → scan length,
scheduler → different step ops, gate → second scan, controller structure →
different edit ops).

``key_fn`` swaps the key derivation under test; the regression test masks
a jaxpr-affecting component through it and asserts the sweep catches the
seeded omission (the acceptance criterion for this checker).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Optional, Tuple

#: Base request every field perturbs against: a 2-prompt replace edit (so
#: controller-shaping fields are live) with no blend/equalizer (so adding
#: them is a structure change). Word counts match across prompt variants —
#: 'replace' requires aligned token counts.
BASE = dict(
    request_id="ck-base",
    prompt="a cat riding a bike",
    target="a dog riding a bike",
    mode="replace",
    steps=3,
    scheduler="ddim",
    seed=11,
    guidance=7.5,
)

#: field -> (variant value, extra overrides applied to BOTH sides of the
#: comparison — context a field needs to be meaningful). The extras may
#: also override the field's own base value (``blend_resolution`` defaults
#: to 16, which no TINY attention site stores). Every Request field MUST
#: appear here — the sweep errors on gaps, so extending the schema forces
#: a decision about program identity.
VARIANTS: Dict[str, Tuple[object, dict]] = {
    "request_id": ("ck-other", {}),
    "prompt": ("a pig riding a bike", {}),
    "target": ("a fox riding a bike", {}),
    "mode": ("refine", {}),
    "cross_steps": (0.5, {}),
    "self_steps": (0.7, {}),
    "blend_words": ("bike", {"blend_resolution": 8}),
    "equalizer": ("bike=2.0", {}),
    # blend_resolution shapes the LocalBlend mask pooling, so its own
    # comparison needs a blend in the base — and a base resolution TINY
    # actually stores (8, not the schema default 16).
    "blend_resolution": (4, {"blend_words": "bike",
                             "blend_resolution": 8}),
    "seed": (7, {}),
    "steps": (4, {}),
    "scheduler": ("dpm", {}),
    "guidance": (3.0, {}),
    "negative_prompt": ("blurry", {}),
    "gate": (0.5, {}),
    # ISSUE 15: a NON-uniform reuse schedule (a uniform one would
    # normalize onto the plain gate and be a deliberate no-op). At the
    # base's steps=3 this resolves to cfg_gate=2 with one early cross
    # flip and the self sites inherited from step 2 — segmented
    # programs, so the jaxpr fingerprint moves with the key.
    "schedule": ({"cfg_gate": 2,
                  "cross": {"*": 2, "cross_attn/down1": 1},
                  "self": {"*": 2}}, {}),
    "arrival_ms": (125.0, {}),
    "deadline_ms": (5000.0, {}),
    "priority": (3, {}),
    # SLO scheduling metadata (ISSUE 12): pure scheduler inputs — they
    # must change neither the program nor any compile key (tiers must
    # not fragment programs; the tier joins the *batch* key only, and
    # only under an active SloConfig).
    "tenant": ("acme", {}),
    "tier": ("premium", {}),
}


@dataclasses.dataclass
class FieldVerdict:
    field: str
    program_changed: bool
    key_changed: bool

    @property
    def ok(self) -> bool:
        return self.program_changed == self.key_changed

    @property
    def problem(self) -> str:
        if self.ok:
            return ""
        if self.program_changed:
            return ("changes the traced program but NOT compile_key — "
                    "ProgramCache poisoning: two requests differing only "
                    "in this field would share one compiled program")
        return ("changes compile_key but NOT the traced program — "
                "retracing churn: identical programs split across cache "
                "keys and batching buckets")

    def format(self) -> str:
        marks = (f"program={'Δ' if self.program_changed else '='} "
                 f"key={'Δ' if self.key_changed else '='}")
        return (f"{'ok  ' if self.ok else 'FAIL'} {self.field:18s} {marks}"
                + (f"  {self.problem}" if not self.ok else ""))


def _request(overrides: dict):
    from ..serve.request import Request

    return Request(**{**BASE, **overrides})


def _overrides_key(overrides: dict) -> Tuple:
    """Hashable fingerprint-cache key for an override set — JSON-object
    values (the ``schedule`` spec) canonicalize through a sorted dump."""
    import json

    def canon(v):
        return (json.dumps(v, sort_keys=True)
                if isinstance(v, (dict, list)) else v)

    return tuple(sorted((k, canon(v)) for k, v in overrides.items()))


def _program_fingerprint(pipe, prep) -> str:
    """Hash of the serve batch program this prepared request would compile
    (bucket 1 — bucket only scales the group axis, per-field identity is
    bucket-independent). Mirrors ``serve.programs.SweepRunner``: same
    encode calls, same ``_sweep_jit`` entry, same static arguments."""
    import jax
    import jax.numpy as jnp

    from ..engine.sampler import encode_prompts, init_latent
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.sweep import _sweep_jit

    req = prep.request
    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(prep.controller)
    schedule = sched_mod.schedule_from_config(req.steps, cfg.scheduler,
                                              kind=req.scheduler)
    cond = encode_prompts(pipe, list(req.prompts))
    uncond = encode_prompts(pipe,
                            [req.negative_prompt or ""] * len(req.prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)[None]
    _, lat = init_latent(None, pipe.latent_shape,
                         jax.random.PRNGKey(req.seed), len(req.prompts))
    lat = lat[None]
    ctrl = (None if prep.controller is None else jax.tree_util.tree_map(
        lambda x: jnp.stack([x]), prep.controller))
    gs = jnp.float32(req.guidance)

    def run(up, vp, ctx, lat, ctrl, gs):
        return _sweep_jit(up, vp, cfg, layout, schedule, req.scheduler,
                          ctx, lat, ctrl, gs, None, progress=False,
                          gate=prep.gate_step, metrics=False,
                          reuse=prep.schedule)

    jaxpr = jax.make_jaxpr(run)(pipe.unet_params, pipe.vae_params, ctx,
                                lat, ctrl, gs)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


#: The phase-key sweep's base: the same request GATED (steps=4 so
#: gate=0.5 → step 2 leaves both phases ≥ 2 steps) — the disaggregated
#: pool keys only exist for gated requests. Field variants that need a
#: different value under this base override VARIANTS here.
PHASE_EXTRA = {"gate": 0.5, "steps": 4}
PHASE_VARIANT_OVERRIDES: Dict[str, Tuple[object, dict]] = {
    # The gated base pins steps=4 and gate=0.5, so the plain variants
    # (steps=4, gate=0.5) would be no-ops; these move them instead:
    # steps 4→5 changes both pool scan lengths, gate 0.5→0.75 moves the
    # boundary (phase-1 grows, phase-2 shrinks) — THE hand-off regression
    # this sweep exists for: a gate change that altered a phase program
    # but not its key would poison the pool cache.
    "steps": (5, {}),
    "gate": (0.75, {}),
    # ISSUE 15: under the gated phase base the schedule comparison runs
    # schedule-vs-schedule (gate and schedule are mutually exclusive, so
    # the extras swap the base's gate for an equivalent-boundary
    # schedule). Base and variant differ ONLY in WHICH cross site flips
    # early — a phase-1-only cell: the phase-1 program and key must both
    # move, while the phase-2 view of both collapses to the uniform
    # table (key component None) and the phase-2 program stays put —
    # the projection-correctness regression for the split keys.
    "schedule": ({"cfg_gate": 2,
                  "cross": {"*": 2, "cross_attn/down3": 1},
                  "self": {"*": None}},
                 {"gate": None,
                  "schedule": {"cfg_gate": 2,
                               "cross": {"*": 2, "cross_attn/down1": 1},
                               "self": {"*": None}}}),
}


def _phase_fingerprints(pipe, prep) -> Tuple[str, str]:
    """Hashes of the two POOL programs this gated prepared request would
    compile (bucket 1). Mirrors ``serve.programs.Phase1Runner`` /
    ``Phase2Runner``: same input construction, same jitted entries, same
    static arguments — including the phase-2 controller reduction."""
    import jax
    import jax.numpy as jnp

    from ..engine.sampler import (encode_prompts, init_latent,
                                  phase2_controller)
    from ..models.config import unet_layout
    from ..ops import schedulers as sched_mod
    from ..parallel.sweep import _sweep_phase1_jit, _sweep_phase2_jit
    from ..serve.handoff import carry_template

    req = prep.request
    cfg = pipe.config
    layout = unet_layout(cfg.unet).for_readers(prep.controller)
    schedule = sched_mod.schedule_from_config(req.steps, cfg.scheduler,
                                              kind=req.scheduler)
    cond = encode_prompts(pipe, list(req.prompts))
    uncond = encode_prompts(pipe,
                            [req.negative_prompt or ""] * len(req.prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)[None]
    _, lat = init_latent(None, pipe.latent_shape,
                         jax.random.PRNGKey(req.seed), len(req.prompts))
    lat = lat[None]
    ctrl = (None if prep.controller is None else jax.tree_util.tree_map(
        lambda x: jnp.stack([x]), prep.controller))
    gs = jnp.float32(req.guidance)

    # Mirror the pool runners exactly: each phase program is keyed (and
    # traced) with its PROJECTED schedule component from the split key —
    # None (plain gate) when the view collapsed to the uniform table.
    from ..engine.reuse import ReuseSchedule

    def view_sched(phase_key):
        skey = phase_key[-1]
        return None if skey is None else ReuseSchedule.from_key(skey)

    reuse1 = view_sched(prep.phase1_key)
    reuse2 = view_sched(prep.phase2_key)

    def run1(up, ctx, lat, ctrl, gs):
        return _sweep_phase1_jit(up, cfg, layout, schedule, req.scheduler,
                                 ctx, lat, ctrl, gs, progress=False,
                                 gate=prep.gate_step, metrics=False,
                                 reuse=reuse1)

    fp1 = jax.make_jaxpr(run1)(pipe.unet_params, ctx, lat, ctrl, gs)

    # carry_template returns the hand-off unit {"carry", "ctx"}; the jit
    # takes the sampler carry and the cond context as separate arguments
    # (mirroring Phase2Runner's unpack).
    carry = jax.tree_util.tree_map(lambda x: jnp.stack([x]),
                                   carry_template(pipe, prep)["carry"])
    p2 = phase2_controller(prep.controller)
    p2_g = (None if p2 is None else jax.tree_util.tree_map(
        lambda x: jnp.stack([x]), p2))

    def run2(up, vp, ctx_c, carry, ctrl, gs):
        return _sweep_phase2_jit(up, vp, cfg, layout, schedule,
                                 req.scheduler, ctx_c, carry, ctrl, gs,
                                 progress=False, gate=prep.gate_step,
                                 metrics=False, reuse=reuse2)

    fp2 = jax.make_jaxpr(run2)(pipe.unet_params, pipe.vae_params,
                               cond[None], carry, p2_g, gs)
    return (hashlib.sha256(str(fp1).encode()).hexdigest(),
            hashlib.sha256(str(fp2).encode()).hexdigest())


def check_phase_keys(pipe=None,
                     key1_fn: Optional[Callable] = None,
                     key2_fn: Optional[Callable] = None,
                     fields: Optional[List[str]] = None
                     ) -> List[FieldVerdict]:
    """The completeness sweep over the SPLIT per-phase pool keys: every
    Request field is perturbed against a *gated* base, the two pool
    programs each variant would compile are traced, and both directions
    must hold per field per pool — a field that changes a pool program
    must change that pool's compile key (else: pool-cache poisoning, the
    hand-off serving requests a mismatched program), and one that doesn't
    must not (else: retracing churn and lost phase-2 packing). Verdicts
    come back as ``<field>@phase1`` / ``<field>@phase2``.

    ``key1_fn``/``key2_fn`` override the keys under test (the regression
    hook: masking the gate from ``phase2_key`` must be caught as
    poisoning for exactly the ``gate`` field)."""
    from ..serve.request import Request, prepare

    if pipe is None:
        from .contracts import tiny_pipeline

        pipe = tiny_pipeline()
    key1_fn = key1_fn or (lambda prep: prep.phase1_key)
    key2_fn = key2_fn or (lambda prep: prep.phase2_key)

    declared = {f.name for f in dataclasses.fields(Request)}
    missing = declared - set(VARIANTS)
    if missing:
        raise ValueError(
            f"Request field(s) {sorted(missing)} have no compile-key sweep "
            "variant: add them to analysis.compile_key.VARIANTS so the "
            "completeness check covers the new schema")

    todo = fields if fields is not None else sorted(VARIANTS)
    fp_cache: Dict[Tuple, Tuple[str, str]] = {}

    def fingerprint(overrides: dict):
        prep = prepare(_request({**PHASE_EXTRA, **overrides}), pipe)
        assert prep.gated, ("phase-key sweep base must stay gated; "
                            f"overrides {overrides} ungated it")
        cache_key = _overrides_key(overrides)
        if cache_key not in fp_cache:
            fp_cache[cache_key] = _phase_fingerprints(pipe, prep)
        return fp_cache[cache_key], key1_fn(prep), key2_fn(prep)

    verdicts = []
    for field in todo:
        variant, extra = PHASE_VARIANT_OVERRIDES.get(field, VARIANTS[field])
        (base1, base2), bk1, bk2 = fingerprint(dict(extra))
        (var1, var2), vk1, vk2 = fingerprint({**extra, field: variant})
        verdicts.append(FieldVerdict(field=f"{field}@phase1",
                                     program_changed=var1 != base1,
                                     key_changed=vk1 != bk1))
        verdicts.append(FieldVerdict(field=f"{field}@phase2",
                                     program_changed=var2 != base2,
                                     key_changed=vk2 != bk2))
    return verdicts


def check_compile_key(pipe=None,
                      key_fn: Optional[Callable] = None,
                      fields: Optional[List[str]] = None
                      ) -> List[FieldVerdict]:
    """Sweep every Request field; returns one :class:`FieldVerdict` each.

    ``key_fn(prepared) -> hashable`` overrides the key under test (default:
    the real ``prepared.compile_key``) — the masking hook the regression
    test uses. ``fields`` narrows the sweep. Raises ``ValueError`` when a
    Request field has no sweep variant (schema grew past the checker)."""
    from ..serve.request import Request, prepare

    if pipe is None:
        from .contracts import tiny_pipeline

        pipe = tiny_pipeline()
    key_fn = key_fn or (lambda prep: prep.compile_key)

    declared = {f.name for f in dataclasses.fields(Request)}
    missing = declared - set(VARIANTS)
    if missing:
        raise ValueError(
            f"Request field(s) {sorted(missing)} have no compile-key sweep "
            "variant: add them to analysis.compile_key.VARIANTS so the "
            "completeness check covers the new schema")
    unknown = set(VARIANTS) - declared
    if unknown:
        raise ValueError(f"sweep variant(s) {sorted(unknown)} no longer "
                         "exist on Request: prune VARIANTS")

    todo = fields if fields is not None else sorted(VARIANTS)
    fp_cache: Dict[Tuple, str] = {}

    def fingerprint(overrides: dict):
        prep = prepare(_request(overrides), pipe)
        cache_key = _overrides_key(overrides)
        if cache_key not in fp_cache:
            fp_cache[cache_key] = _program_fingerprint(pipe, prep)
        return fp_cache[cache_key], key_fn(prep)

    verdicts = []
    for field in todo:
        variant, extra = VARIANTS[field]
        base_fp, base_key = fingerprint(dict(extra))
        var_fp, var_key = fingerprint({**extra, field: variant})
        verdicts.append(FieldVerdict(
            field=field,
            program_changed=var_fp != base_fp,
            key_changed=var_key != base_key))
    return verdicts


# ---------------------------------------------------------------------------
# Content-key completeness (ISSUE 13) — the semantic-cache poisoning guard
# ---------------------------------------------------------------------------

#: Which Request fields determine the request's OUTPUT IMAGES — the
#: checker's own declaration, independent of the hand partition in
#: ``serve.request`` (CONTENT_FIELDS/SCHEDULING_FIELDS), so the two
#: derivations cross-check each other. A field marked True must perturb
#: ``content_key`` (missing ⇒ cache *poisoning*: a hit serves wrong
#: images); a field marked False must not (superfluous ⇒ identical
#: traffic split across cache lines: lost hits). The sweep also fails on
#: any Request field absent from this map — a new schema field cannot
#: dodge the cache-identity decision by omission.
OUTPUT_DETERMINING: Dict[str, bool] = {
    "prompt": True,
    "target": True,
    "mode": True,
    "cross_steps": True,
    "self_steps": True,
    "blend_words": True,
    "equalizer": True,
    "blend_resolution": True,
    "seed": True,
    "steps": True,
    "scheduler": True,
    "guidance": True,
    "negative_prompt": True,
    "gate": True,
    # ISSUE 15: a (non-uniform) reuse schedule changes which site-steps
    # compute — different images. Keyed on the RESOLVED table, so specs
    # resolving identically (and the uniform table vs plain gate=g)
    # share a cache line.
    "schedule": True,
    "request_id": False,
    "arrival_ms": False,
    "deadline_ms": False,
    "priority": False,
    "tenant": False,
    "tier": False,
}


@dataclasses.dataclass
class ContentVerdict:
    field: str
    output_determining: bool
    key_changed: bool

    @property
    def ok(self) -> bool:
        return self.output_determining == self.key_changed

    @property
    def problem(self) -> str:
        if self.ok:
            return ""
        if self.output_determining:
            return ("determines the output images but NOT content_key — "
                    "cache poisoning: a request differing only in this "
                    "field would be served another request's images")
        return ("changes content_key but NOT the output — lost hits: "
                "identical traffic split across cache lines by pure "
                "scheduling metadata")

    def format(self) -> str:
        marks = (f"output={'Δ' if self.output_determining else '='} "
                 f"key={'Δ' if self.key_changed else '='}")
        return (f"{'ok  ' if self.ok else 'FAIL'} {self.field:18s} {marks}"
                + (f"  {self.problem}" if not self.ok else ""))


def check_content_key(pipe=None,
                      key_fn: Optional[Callable] = None,
                      fields: Optional[List[str]] = None
                      ) -> List[ContentVerdict]:
    """The completeness sweep over the semantic cache's ``content_key``
    (ISSUE 13), same idiom as :func:`check_compile_key`: every Request
    field is perturbed against the edit base (so controller-shaping
    fields are live) and both directions must hold per field —
    output-determining fields (:data:`OUTPUT_DETERMINING`) must perturb
    the key, scheduling metadata must not.

    The oracle is the declared map rather than a traced program: seed,
    guidance and prompt change output *values* invisible to any jaxpr
    structure, so there is nothing cheaper than real execution to trace —
    the bitwise half is pinned empirically by the cache-parity drill
    (every cached serve bitwise-identical to its uncached twin) and by
    the value-only field test in tests/test_semcache.py. What this sweep
    stops trusting is the hand *derivation*: the checker's own field map
    is cross-checked against ``serve.request``'s CONTENT/SCHEDULING
    partition, and a schema field missing from either raises.

    ``key_fn(prepared) -> hashable`` overrides the key under test (the
    masking hook: hiding ``seed`` from the key must be caught as
    poisoning for exactly the ``seed`` field)."""
    from ..serve.request import (CONTENT_FIELDS, Request, SCHEDULING_FIELDS,
                                 prepare)

    if pipe is None:
        from .contracts import tiny_pipeline

        pipe = tiny_pipeline()
    key_fn = key_fn or (lambda prep: prep.content_key)

    declared = {f.name for f in dataclasses.fields(Request)}
    for name, covered in (("OUTPUT_DETERMINING map", set(OUTPUT_DETERMINING)),
                          ("compile-key sweep VARIANTS", set(VARIANTS))):
        missing = declared - covered
        if missing:
            raise ValueError(
                f"Request field(s) {sorted(missing)} are missing from the "
                f"{name}: extend analysis.compile_key so the content-key "
                "completeness check covers the new schema")
    # Cross-check the independent derivations: the checker's map vs the
    # serve schema's CONTENT/SCHEDULING partition.
    ours = {f for f, v in OUTPUT_DETERMINING.items() if v}
    theirs = set(CONTENT_FIELDS)
    if ours != theirs or (declared - ours) != set(SCHEDULING_FIELDS):
        raise ValueError(
            f"analysis.compile_key.OUTPUT_DETERMINING disagrees with "
            f"serve.request's CONTENT_FIELDS/SCHEDULING_FIELDS partition "
            f"on {sorted(ours ^ theirs)}: resolve which derivation is "
            "wrong before caching can serve this schema")

    todo = fields if fields is not None else sorted(OUTPUT_DETERMINING)
    verdicts = []
    for field in todo:
        variant, extra = VARIANTS[field]
        base_key = key_fn(prepare(_request(dict(extra)), pipe))
        var_key = key_fn(prepare(_request({**extra, field: variant}), pipe))
        verdicts.append(ContentVerdict(
            field=field,
            output_determining=OUTPUT_DETERMINING[field],
            key_changed=var_key != base_key))
    return verdicts
