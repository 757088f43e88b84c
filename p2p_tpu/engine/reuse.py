"""Per-site per-step reuse schedules — the generalized phase gate (ISSUE 15).

PR 1's single static ``gate`` is the crudest point in the TAD/A-SDM design
space: it flips *all* cross-attention sites from full-CFG compute to cached
reuse at one step. TAD (arXiv 2404.02747) measures that temporal redundancy
differs per attention block, and A-SDM (arXiv 2406.00210) shows self-attn
features can be inherited across adjacent steps — so the win left on the
table is a schedule that decides, **per attention site and per scan step**,
one of three actions:

- **compute-full-CFG** — the site runs normally (and, if it will ever be
  reused, overwrites its cache slot with this step's output);
- **reuse-cross-attn-from-AttnCache** — a cross site returns its cached
  output (the TAD mechanism PR 1 applied uniformly);
- **inherit-feature-from-previous-step** — a self site returns the output
  frozen at its last computed step (A-SDM feature inheritance; mechanically
  the same cache, applied to self-attention sites).

A :class:`ReuseSchedule` is a **frozen static table** (hashable pytree-free
dataclass), so each distinct schedule is ONE compiled program: it joins
``compile_key`` — and the phase-1/phase-2 split keys, via the
:func:`phase1_view`/:func:`phase2_view` projections — exactly like ``gate``
does today. The step where the CFG (uncond) branch drops, ``cfg_gate``, IS
the serve engine's phase boundary: the two-pool hand-off machinery
(``PhaseCarry``/``spill_carry``/``stack_carries``) carries the scheduled
per-site cache state with no new hand-off plumbing.

The **uniform** schedule — every cross site reused from step ``g``, no self
site ever reused, CFG dropped at ``g`` — is ``gate=g``:
:meth:`ReuseSchedule.uniform` builds it from a gate step (``g == S``: nothing
gated, nothing cached — the ungated run), and under the jitted entry points
every run is described by one table. :func:`ReuseSchedule.uniform_gate` is
the inverse, used where a user's schedule is resolved
(``engine.sampler.resolve_reuse``, ``serve.request.prepare``): a uniform table
is keyed as its gate, so it shares one compile key and one pool with plain
``gate=g`` requests.

Execution model: the scan is cut into contiguous **segments** over which the
per-site action vector is constant; each segment is one ``lax.scan`` with a
static site plan (``engine.sampler._phase1_scan`` / ``_phase2_scan``). The
uniform table cuts into one phase-1 segment whose cross sites ``store`` and
one phase-2 segment whose cross sites ``use``. Compile time grows with the
number of distinct flip steps, not with S.

Resblock-level inheritance (the remaining A-SDM axis) is deliberately out of
scope: resnets are not layout sites, so scheduling them is a layout change —
noted in PERF.md as follow-up.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

#: Per-site, per-segment actions. ``store``: compute and overwrite the cache
#: slot with the *conditional half* of the CFG-doubled output (the PR-1
#: phase-1 capture — the leaf a post-``cfg_gate`` segment consumes).
#: ``store_all``: compute and overwrite with the full batch (a site reused
#: while CFG is still active needs both halves; post-gate the "full batch"
#: is the cond-only batch, so ``store_all`` is also the post-gate store).
#: ``use``: return the cached output, computing nothing. ``off``: plain
#: compute, no cache slot.
MODE_OFF = "off"
MODE_STORE = "store"
MODE_STORE_ALL = "store_all"
MODE_USE = "use"

_SITE_NAME_RE = re.compile(r"^(cross_attn|self_attn)/(down|mid|up)\d+$")

_SPEC_KEYS = {"version", "cfg_gate", "cross", "self", "comment", "provenance"}


def site_name(meta) -> str:
    """The canonical name of one attention site — identical to the
    ``jax.named_scope`` the U-Net wraps the site in (``cross_attn/down3``),
    so a schedule artifact, a Perfetto trace and the cost attribution all
    speak the same site vocabulary."""
    kind = "cross_attn" if meta.is_cross else "self_attn"
    return f"{kind}/{meta.place}{meta.layer_idx}"


def site_names(layout, kind: str) -> Tuple[str, ...]:
    """Site names of one kind (``'cross'``/``'self'``) in call order — the
    order the per-kind reuse tuples of a :class:`ReuseSchedule` index."""
    cross = kind == "cross"
    return tuple(site_name(m) for m in layout.metas if m.is_cross == cross)


@dataclasses.dataclass(frozen=True)
class ReuseSchedule:
    """The resolved static reuse table for one scan length.

    ``steps`` is the scan length S (PLMS includes its warm-up step, same as
    ``resolve_gate``). ``cfg_gate`` ∈ [1, S] is the first step without the
    uncond batch half (S = CFG everywhere — a schedule may cache sites
    without ever dropping CFG). ``cross``/``selfa`` hold one entry per
    cross/self attention site in layout call order: the first scan step the
    site is served from its cache (S = never reused). All static ints, so
    the whole table is hashable and rides ``jax.jit`` static arguments."""

    steps: int
    cfg_gate: int
    cross: Tuple[int, ...]
    selfa: Tuple[int, ...]

    def __post_init__(self):
        s = self.steps
        if s < 1:
            raise ValueError(f"schedule needs steps >= 1, got {s}")
        if not 1 <= self.cfg_gate <= s:
            raise ValueError(f"cfg_gate {self.cfg_gate} outside [1, {s}]")
        for kind, table in (("cross", self.cross), ("self", self.selfa)):
            for i, r in enumerate(table):
                if not 1 <= r <= s:
                    raise ValueError(
                        f"{kind} site {i}: reuse step {r} outside [1, {s}] "
                        f"(use {s} for 'never')")

    @property
    def gated(self) -> bool:
        """Does this schedule drop the CFG branch before the end — i.e.
        does it cross the serve engine's two-pool phase boundary?"""
        return self.cfg_gate < self.steps

    @classmethod
    def uniform(cls, gate: int, steps: int, layout) -> "ReuseSchedule":
        """``gate=g`` as a table: CFG drops at ``gate``, every cross site is
        served from its cache from there on, no self site ever is.
        ``gate == steps`` is the ungated run: nothing dropped, nothing
        cached. ``layout`` gives the site counts."""
        n_cross = sum(1 for m in layout.metas if m.is_cross)
        return cls(steps=steps, cfg_gate=gate, cross=(gate,) * n_cross,
                   selfa=(steps,) * (len(layout.metas) - n_cross))

    @property
    def uniform_gate(self) -> Optional[int]:
        """The gate step this table is :meth:`uniform` of, or None.
        ``steps`` is the ungated run. Where a user's schedule is resolved, a
        uniform table is keyed as its gate, so that it compiles and pools
        with plain ``gate=g`` requests."""
        g = self.cfg_gate
        if any(r != self.steps for r in self.selfa):
            return None
        return g if all(r == g for r in self.cross) else None

    def key(self) -> Tuple:
        """The schedule's compile-key component: the table CONTENTS, so two
        identical tables loaded from different files derive equal keys (and
        pool), while tables differing in a single site-step entry differ."""
        return ("sched", self.steps, self.cfg_gate, self.cross, self.selfa)

    @classmethod
    def from_key(cls, key: Tuple) -> "ReuseSchedule":
        """Rebuild the schedule from its :meth:`key` tuple — the serve
        runners reconstruct the static table from the compile key alone."""
        tag, steps, cfg_gate, cross, selfa = key
        assert tag == "sched", key
        return cls(steps=steps, cfg_gate=cfg_gate, cross=tuple(cross),
                   selfa=tuple(selfa))

    def sites_cached(self) -> Dict[str, int]:
        """How many sites the schedule ever serves from cache, by kind."""
        return {
            "cross": sum(1 for r in self.cross if r < self.steps),
            "self": sum(1 for r in self.selfa if r < self.steps),
            "cross_sites": len(self.cross),
            "self_sites": len(self.selfa),
        }


def phase1_view(sched: ReuseSchedule) -> ReuseSchedule:
    """The phase-1 projection: the part of the table that shapes the
    program for steps ``[0, cfg_gate)``. Reuse steps at or past the gate
    collapse to the gate (phase 1 only sees "stores until the boundary");
    never-reused stays never (the site has no cache leaf at all). Two
    schedules with equal phase-1 views compile — and must pool — the same
    phase-1 program, so this projection (via :meth:`ReuseSchedule.key`) is
    the ``phase1_key`` schedule component."""
    g, s = sched.cfg_gate, sched.steps

    def clamp(r: int) -> int:
        return r if r < g else (g if r < s else s)

    return ReuseSchedule(steps=s, cfg_gate=g,
                         cross=tuple(clamp(r) for r in sched.cross),
                         selfa=tuple(clamp(r) for r in sched.selfa))


def phase2_view(sched: ReuseSchedule) -> ReuseSchedule:
    """The phase-2 projection: the part of the table that shapes the
    program for steps ``[cfg_gate, S)``. Reuse steps before the gate
    collapse to the gate (phase 2 only sees "reused from entry"); a site
    that flips inside phase 2 keeps its exact step. Schedules differing
    only before the gate share a phase-2 view — their phase-2 lanes pack
    into one pool program (the ``phase2_key`` schedule component)."""
    g, s = sched.cfg_gate, sched.steps

    def clamp(r: int) -> int:
        return r if r >= g else g

    return ReuseSchedule(steps=s, cfg_gate=g,
                         cross=tuple(clamp(r) if r < s else s
                                     for r in sched.cross),
                         selfa=tuple(clamp(r) if r < s else s
                                     for r in sched.selfa))


# ---------------------------------------------------------------------------
# Spec: the user-facing (JSON) schedule table
# ---------------------------------------------------------------------------


def validate_spec(spec: dict) -> None:
    """Structural validation of a schedule spec — admission-time cheap, no
    layout needed. A spec is a JSON object::

        {"version": 1,
         "cfg_gate": 0.5 | <int step> | "auto" | null,
         "cross": {"*": 0.5, "cross_attn/down3": 0.25, ...},
         "self":  {"*": null, "self_attn/up8": 0.85, ...}}

    Fractions are of the scan length (resolved per request, like ``gate``);
    ``null`` means never reused (for ``cfg_gate``: CFG never drops). Site
    keys must be canonical site names (the ``jax.named_scope`` vocabulary)
    or ``"*"`` (the default for unlisted sites); names that parse as a
    site of ANOTHER model's layout are tolerated at resolve time (one
    committed artifact serves models with different site counts), anything
    else is an error — the honored-flags discipline."""
    if not isinstance(spec, dict):
        raise ValueError(f"schedule spec must be a JSON object, "
                         f"got {type(spec).__name__}")
    unknown = set(spec) - _SPEC_KEYS
    if unknown:
        raise ValueError(f"unknown schedule spec key(s) {sorted(unknown)}; "
                         f"valid: {sorted(_SPEC_KEYS)}")
    if spec.get("version", 1) != 1:
        raise ValueError(f"unsupported schedule spec version "
                         f"{spec.get('version')!r} (expected 1)")
    _check_step_spec(spec.get("cfg_gate"), "cfg_gate", allow_auto=True)
    for kind in ("cross", "self"):
        table = spec.get(kind)
        if table is None:
            continue
        if not isinstance(table, dict):
            raise ValueError(f"schedule spec {kind!r} must be an object "
                             f"mapping site names to steps, got "
                             f"{type(table).__name__}")
        for name, v in table.items():
            if name != "*" and not _SITE_NAME_RE.match(name):
                raise ValueError(
                    f"schedule spec {kind!r} has invalid site key {name!r}"
                    " (expected '*' or a canonical site name like "
                    "'cross_attn/down3')")
            if name != "*" and not name.startswith(
                    "cross_attn/" if kind == "cross" else "self_attn/"):
                raise ValueError(
                    f"schedule spec {kind!r} key {name!r} names a site of "
                    "the other kind")
            _check_step_spec(v, f"{kind}[{name}]", allow_auto=False)


def _check_step_spec(v, what: str, allow_auto: bool) -> None:
    if v is None:
        return
    if isinstance(v, str):
        if allow_auto and v == "auto":
            return
        raise ValueError(f"schedule {what} must be null, a fraction or a "
                         f"step index{', or auto' if allow_auto else ''}, "
                         f"got {v!r}")
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise ValueError(f"schedule {what} must be numeric, got {v!r}")
    if isinstance(v, float) and not 0.0 < v <= 1.0:
        raise ValueError(f"schedule {what} fraction {v} outside (0, 1]")
    if isinstance(v, int) and v < 1:
        raise ValueError(f"schedule {what} step {v} must be >= 1")


def _resolve_step(v, num_scan: int, default: int,
                  controller=None) -> int:
    """One spec cell → a static scan step, ``resolve_gate`` semantics:
    float = fraction of the scan (rounded), int = absolute step, None =
    ``default``. Clamped to [1, S]."""
    if v is None:
        return default
    if v == "auto":
        from ..controllers.base import controller_step_window

        return min(num_scan,
                   max(num_scan // 2,
                       controller_step_window(controller, num_scan), 1))
    if isinstance(v, float):
        # Same boundary discipline as resolve_gate: a fraction that
        # rounds outside [1, S] is a rejected typo, never a silent clamp.
        step = int(round(v * num_scan))
    else:
        step = int(v)
    if not 1 <= step <= num_scan:
        raise ValueError(f"schedule step {v!r} resolves to {step}, "
                         f"outside [1, {num_scan}]")
    return step


def resolve_schedule(spec, layout, num_scan: int,
                     controller=None) -> ReuseSchedule:
    """Resolve a spec (or pass through an already-resolved table) against a
    concrete layout and scan length. Unlisted sites take the kind's ``"*"``
    default; without one, cross sites default to the ``cfg_gate`` (the
    uniform gate behavior) and self sites to never-reused — so
    ``{"cfg_gate": 0.5}`` alone IS the PR-1 ``gate=0.5``."""
    if isinstance(spec, ReuseSchedule):
        if spec.steps != num_scan:
            raise ValueError(
                f"resolved schedule is for a {spec.steps}-step scan, "
                f"request runs {num_scan}")
        n_cross = sum(1 for m in layout.metas if m.is_cross)
        n_self = sum(1 for m in layout.metas if not m.is_cross)
        if len(spec.cross) != n_cross or len(spec.selfa) != n_self:
            raise ValueError(
                f"resolved schedule has {len(spec.cross)} cross / "
                f"{len(spec.selfa)} self entries; layout has "
                f"{n_cross}/{n_self}")
        return spec
    validate_spec(spec)
    cfg_gate = _resolve_step(spec.get("cfg_gate"), num_scan, num_scan,
                             controller=controller)

    def table(kind: str, metas, default: int) -> Tuple[int, ...]:
        raw = dict(spec.get(kind) or {})
        # An EXPLICIT null means "never reused" — distinct from an absent
        # key, which falls back to the kind default (cfg_gate for cross,
        # never for self). ``{"*": null}`` therefore pins every unlisted
        # site of the kind to never.
        if "*" in raw:
            star = raw.pop("*")
            kind_default = (num_scan if star is None
                            else _resolve_step(star, num_scan, default))
        else:
            kind_default = default
        out = []
        for m in metas:
            name = site_name(m)
            if name in raw:
                v = raw.pop(name)
                out.append(num_scan if v is None
                           else _resolve_step(v, num_scan, kind_default))
            else:
                out.append(kind_default)
        # Leftover names target sites this layout doesn't have (an
        # artifact shared across models) — already shape-validated by
        # validate_spec, so they are silently inapplicable here.
        return tuple(out)

    cross = table("cross", [m for m in layout.metas if m.is_cross],
                  default=cfg_gate)
    selfa = table("self", [m for m in layout.metas if not m.is_cross],
                  default=num_scan)
    return ReuseSchedule(steps=num_scan, cfg_gate=cfg_gate, cross=cross,
                         selfa=selfa)


def load_spec(path: str) -> dict:
    """Load + validate a schedule artifact (``tools/schedules/*.json``)."""
    with open(path) as f:
        spec = json.load(f)
    validate_spec(spec)
    return spec


# ---------------------------------------------------------------------------
# Segmentation: the static per-segment site plans the executor scans with
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    """One contiguous scan range with a constant per-site action vector.
    ``plan`` has one mode per layout site in call order (the
    ``apply_unet(site_plan=)`` argument); sites whose mode is not ``off``
    own one cache leaf each, in the same order."""

    start: int
    stop: int
    cfg: bool                  # uncond batch half present (CFG active)
    plan: Tuple[str, ...]

    @property
    def stores(self) -> bool:
        """Does a site write its cache slot in this segment? Then the cache
        rides the scan's carry; otherwise it is loop-invariant and the
        scan's body closes over it (engine.sampler)."""
        return any(m in (MODE_STORE, MODE_STORE_ALL) for m in self.plan)


def _reuse_step(sched: ReuseSchedule, meta, cross_idx: int,
                self_idx: int) -> int:
    return (sched.cross[cross_idx] if meta.is_cross
            else sched.selfa[self_idx])


def _site_table(layout, sched: ReuseSchedule) -> List[int]:
    """Per layout site (call order): its resolved reuse step."""
    out, ci, si = [], 0, 0
    for m in layout.metas:
        if m.is_cross:
            out.append(sched.cross[ci])
            ci += 1
        else:
            out.append(sched.selfa[si])
            si += 1
    return out


def cached_sites(layout, sched: ReuseSchedule) -> List[int]:
    """Layout indices of sites that ever hit their cache (r < S) — the
    sites that own a cache leaf, in call order."""
    return [i for i, r in enumerate(_site_table(layout, sched))
            if r < sched.steps]


def segments(layout, sched: ReuseSchedule, phase: int) -> List[Segment]:
    """Cut one phase of the scan into constant-plan segments.

    ``phase=1``: steps ``[0, cfg_gate)`` (CFG active). ``phase=2``: steps
    ``[cfg_gate, S)`` (single branch). Within each segment every site has a
    static mode; flips happen only at segment boundaries, so each segment
    compiles as one ``lax.scan``."""
    s, g = sched.steps, sched.cfg_gate
    table = _site_table(layout, sched)
    lo, hi = (0, g) if phase == 1 else (g, s)
    if lo >= hi:
        return []
    cuts = sorted({lo, hi} | {r for r in table if lo < r < hi})
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        plan = []
        for i, m in enumerate(layout.metas):
            r = table[i]
            if r >= s:
                plan.append(MODE_OFF)
            elif a >= r:
                plan.append(MODE_USE)
            elif phase == 1 and r >= g:
                # Flips at-or-after the boundary: phase 1 captures the
                # cond half every step, exactly the PR-1 phase-1 store.
                plan.append(MODE_STORE)
            else:
                # Flips inside this phase: keep the full current batch
                # (2B under CFG, B past it) so the flip segment can serve
                # the site whichever batch shape is live.
                plan.append(MODE_STORE_ALL)
        segs.append(Segment(start=a, stop=b, cfg=(phase == 1),
                            plan=tuple(plan)))
    return segs


def lower_kernel_plan(layout, sched: ReuseSchedule, controller, kernels,
                      phase: int) -> List[Tuple[Segment, Tuple[str, ...]]]:
    """Static kernel lowering of one phase: for every constant-plan segment
    (:func:`segments`), the attention variant each site compiles to under
    ``kernels`` (a ``kernels.KernelConfig`` or None) — the
    ``kernels.dispatch.site_variant`` vocabulary (``use`` / ``flash`` /
    ``fused-edit`` / ``materialized``). Pure trace-time introspection over
    the same static inputs the executors consume: what
    ``_phase1_scan`` / ``_phase2_scan`` + ``apply_unet`` will actually lower, without
    building the program. ``use`` segments lower to the cache side-input
    (no attention math); ``store``/``store_all`` segments capture the site
    output *after* whichever attention variant runs — the fused
    side-output — so a controller-edited site keeps its fused-edit
    lowering while storing."""
    from ..kernels.dispatch import site_variant

    out = []
    for seg in segments(layout, sched, phase):
        variants = tuple(
            site_variant(kernels, controller, m, mode)
            for m, mode in zip(layout.metas, seg.plan))
        out.append((seg, variants))
    return out


def init_schedule_cache(layout, sched: ReuseSchedule, batch_cond: int,
                        phase: int, dtype) -> Tuple:
    """Zero cache leaves for every ever-cached site, in call order.

    ``phase=1`` leaves are the CFG-phase shapes: a site reused *while CFG
    is active* (r < cfg_gate) caches the full doubled batch ``(2B, P, C)``;
    every other cached site holds the conditional half ``(B, P, C)`` (the
    PR-1 AttnCache shape). ``phase=2`` leaves are all ``(B, P, C)`` — the
    hand-off shapes ``slice_cache_to_cond`` produces at the boundary."""
    import jax.numpy as jnp

    table = _site_table(layout, sched)
    leaves = []
    for i in cached_sites(layout, sched):
        m = layout.metas[i]
        if m.channels <= 0:
            raise ValueError(
                f"site {site_name(m)} has no channel info (layout built "
                "from 5-tuple specs); the reuse cache needs channels — "
                "rebuild the layout via unet_attn_specs")
        b = batch_cond
        if phase == 1 and table[i] < sched.cfg_gate:
            b = 2 * batch_cond
        leaves.append(jnp.zeros((b, m.pixels, m.channels), dtype))
    return tuple(leaves)


def slice_cache_to_cond(layout, sched: ReuseSchedule, cache: Tuple,
                        batch_cond: int) -> Tuple:
    """The phase boundary's cache hand-off: leaves captured at the full
    CFG batch (sites reused under CFG) drop their uncond half, so every
    leaf crossing the hand-off is ``(B, P, C)`` — the shape the phase-2
    pool program (and the journal spill template) expects."""
    table = _site_table(layout, sched)
    out = []
    for leaf, i in zip(cache, cached_sites(layout, sched)):
        if table[i] < sched.cfg_gate:
            leaf = leaf[batch_cond:]
        out.append(leaf)
    return tuple(out)


# ---------------------------------------------------------------------------
# Schedule-vs-controller-window conflicts (generalizes warn_gate_truncation)
# ---------------------------------------------------------------------------

_warned_conflicts: set = set()


def warn_schedule_conflicts(sched: ReuseSchedule, layout, controller,
                            num_scan: int) -> List[str]:
    """Warn — once per distinct conflict set — when a schedule reuses a
    site *inside* its controller's active edit window: a reused site's
    attention probabilities are never materialized, so the edit at that
    site is silently dropped past the reuse step. The generalization of
    ``warn_gate_truncation``: instead of one all-site gate-vs-window
    check, every site is checked against the window that governs its KIND
    (cross sites vs the cross-replace schedule's support, self sites vs
    the self-injection window), and the warning NAMES the offending
    sites. Returns the offending site names (for tests and the search
    tool's pruning)."""
    from ..controllers.base import (controller_edit_windows,
                                   controller_step_window)

    if controller is None:
        return []
    if getattr(controller, "store", False) and sched.gated:
        # Same explicit-store caveat as the gate path (and independent of
        # any edit window — a pure observability store has none):
        # accumulation stops at the CFG boundary.
        import warnings

        warnings.warn(
            f"schedule cfg_gate {sched.cfg_gate} < {num_scan}: the "
            "attention store stops accumulating at the CFG boundary, so "
            "averaged maps cover phase 1 only", stacklevel=3)
    window = controller_step_window(controller, num_scan)
    cross_end, self_end = controller_edit_windows(controller, num_scan)
    if window <= 0:
        return []
    table = _site_table(layout, sched)
    offending = []
    for i, r in enumerate(table):
        m = layout.metas[i]
        end = cross_end if m.is_cross else self_end
        if r < end:
            offending.append(f"{site_name(m)}@{r}<{end}")
    if sched.cfg_gate < window:
        offending.append(f"cfg_gate@{sched.cfg_gate}<{window}")
    if offending:
        key = (tuple(offending), window)
        if key not in _warned_conflicts:
            _warned_conflicts.add(key)
            import warnings

            warnings.warn(
                f"reuse schedule conflicts with the controller's edit "
                f"window (ends at step {window}): "
                f"{', '.join(offending)} reuse/truncate inside it — "
                "attention edits at those sites are dropped past their "
                "reuse step. Move the reuse steps to >= the window end "
                "(or shorten the edit window) to keep P2P semantics.",
                stacklevel=3)
    return offending
