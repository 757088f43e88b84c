"""Device time by program scope, the program's entry spans and its compile
ledger: what the readers of PR 27's per-layer metrics share.

The TPU's trace names a device operation by its HLO instruction and its
module and carries no scope names (PERF.md, Findings); the program keeps, for
every program it launched, what is needed to map instruction names back to
its ``jax.named_scope``s (``p2p_tpu/obs/launches.py:scope_index``, built from
the compiled program's text when asked). ``load`` joins the two: every leaf
operation of the traced window gets the scope of its instruction, its class
among the step's parts, and whether the fusion it ran as has members in
more than one scope. On a traced run the whole tree goes to stderr.

A program that offers no index (any tree before PR 27) makes every reader
here return None: the result line then leaves the metric out.
"""

from __future__ import annotations

import re
import resource
import sys
import time
from dataclasses import dataclass, field
from types import SimpleNamespace

from . import trace as T

#: The step's disjoint parts, by the metric that reports each; the sixth,
#: ``embed_mod``, is a transformer denoiser's own and no metric reads it yet.
PARTS = ("resblock", "self_attn", "cross_attn", "ff", "outside_unet", "embed_mod")
_RESBLOCK = re.compile(
    r"^unet/(?:conv_in|conv_out|time_embed"
    r"|(?:down|mid|up)\d+/(?:res\d+|downsample|upsample|skip_concat))$")
_FF = re.compile(r"^unet/(?:down|mid|up)\d+/attn\d+/(?:ff|proj_in|proj_out)$"
                 r"|^dit/block\d+/ff$")
#: Under this share of scoped device time the index is not the program's
#: that ran (stale cache entry, renamed instructions): nothing else is read.
SCOPED_FLOOR_PCT = 90.0


def part_of(scope) -> str:
    """Which of the step's parts a scope path belongs to.

    A U-Net's: ``unet/<place><level>/res<i>`` and the convolutions around
    the levels are ``resblock``, ``.../attn<i>/{ff,proj_in,proj_out}``
    ``ff``. A transformer denoiser's scopes are ``dit/block<i>/`` followed
    by ``self_attn/<site>/{qkv,core,out}``, ``cross_attn/<site>/{qkv,core,out}``,
    ``ff`` or ``modulate`` within a block, and ``dit/{patch_embed,
    time_embed, caption_proj, final}`` outside the blocks; its ``ff`` is
    ``ff`` and the rest of ``dit`` is ``embed_mod``, so nothing of it is the
    sampler's. Either denoiser's attention is ``self_attn`` or
    ``cross_attn``. Whatever is not the denoiser's (``sampler/...``, no scope
    at all, a U-Net path cut short of a block) is the sampler's own: the
    parts then sum to the step."""
    if not scope:
        return "outside_unet"
    if "/self_attn/" in scope:
        return "self_attn"
    if "/cross_attn/" in scope:
        return "cross_attn"
    if _FF.match(scope):
        return "ff"
    if _RESBLOCK.match(scope):
        return "resblock"
    if scope == "dit" or scope.startswith("dit/"):
        return "embed_mod"
    return "outside_unet"


def _straddles(scopes, level: int = 2) -> bool:
    """Whether ``scopes`` (a fusion's member scopes, from the index) differ
    in their first ``level`` components: ``unet/down0/res1`` and
    ``unet/down0/attn1/proj_in`` share their second-level scope,
    ``unet/conv_out`` and ``sampler/cfg`` do not."""
    return len({tuple(s.split("/")[:level]) for s in scopes}) > 1


@dataclass
class Row:
    op: T.Op
    scope: str            # "" where the index has no scope for it
    part: str             # which of the step's parts
    ambiguous: bool       # a fusion whose members span two second-level scopes
    crosses_parts: bool   # ... or two of the step's parts


@dataclass
class Scoped:
    rows: list = field(default_factory=list)
    steps: int = 0
    calls: int = 0
    images: int = 0
    ndev: int = 1
    build_s: float = 0.0
    scoped_pct: float = 0.0   # share of the window's device time with a scope

    def loop_ms_per_step(self, part: str):
        """ms a step of the loop's device time in one of the parts."""
        if self.scoped_pct < SCOPED_FLOOR_PCT or not self.steps:
            return None
        ns = sum(r.op.dur for r in self.rows if r.op.loop and r.part == part)
        return ns / self.ndev / self.steps / 1e6

    def ms_per_image(self, prefix: str):
        if self.scoped_pct < SCOPED_FLOOR_PCT or not self.images:
            return None
        ns = sum(r.op.dur for r in self.rows if r.scope.startswith(prefix))
        return ns / self.ndev / self.images / 1e6 if ns else None


def _program_index(run, module: str):
    """``({instruction: scope}, {fusion: {scope: members}})`` of a program
    that ran, from the program (or from ``run.scope_indexes``, which a
    recorded trace brings along); None where there is none, as with a
    program that keeps no launch registry (before PR 27)."""
    recorded = getattr(run, "scope_indexes", None)
    if recorded is not None:
        return recorded.get(module)
    try:
        from p2p_tpu.obs import launches
    except ImportError:
        return None
    return launches.scope_index(module)


def load(run):
    """The ``Scoped`` reduction of the run's trace, made once; None where
    there is no trace or the program offers no scope index."""
    if "_scoped" not in run.__dict__:
        run._scoped = _load(run)
    return run._scoped


def _load(run):
    tr = run.trace_data
    recs = run.traced_records()
    if tr is None or not recs:
        return None
    lo, hi = run.trace_window
    ops = T.leaf_ops(tr, lo, hi)
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    t0 = time.monotonic()
    indexes = {m: _program_index(run, m) for m in {o.module for o in ops}}
    if not any(indexes.values()):
        return None
    work = run.work_of(recs)
    out = Scoped(steps=work["steps"], calls=len(recs), images=work["images"],
                 ndev=max(1, len(tr.devices)), build_s=time.monotonic() - t0)
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    placed = {}           # an instruction runs once a step: place each once
    for o in ops:
        key = (o.module, o.name)
        if key not in placed:
            index, mixed = indexes.get(o.module) or ({}, {})
            scope, members = index.get(o.name, ""), mixed.get(o.name, ())
            placed[key] = (scope, part_of(scope), _straddles(members),
                           len({part_of(s) for s in members}) > 1)
        out.rows.append(Row(o, *placed[key]))
    total = sum(r.op.dur for r in out.rows)
    out.scoped_pct = 100.0 * sum(r.op.dur for r in out.rows if r.scope) / total
    _print(run, out, indexes, (rss1 - rss0) / 1024.0)
    return out


# -- the program's entry spans (its ring) ---------------------------------

def ring_spans(run):
    """``[(id, parent, name, start_ns, end_ns, attrs)]`` of the program's
    finished spans, on ``time.monotonic_ns()``: the harness's clock. None
    where the program's spans are on another clock (before PR 27)."""
    try:
        from p2p_tpu.obs import spans
    except ImportError:
        return None
    events = getattr(run, "ring_events", None) or spans.events()
    out = []
    for e in events:
        if e.get("event") != "span_end":
            continue
        if "t_ns" not in e:
            return None
        end = e["t_ns"]
        out.append((e["span"], e["parent"], e["name"],
                    end - int(e["dur_ms"] * 1e6), end, e))
    return out


def entry_self_ms_per_call(run):
    """Sum of the self times (a span's time less its children's, joined by
    id) of the program's ``entry.*`` and ``sampler.*`` spans that lie inside
    the traced window, per traced call."""
    rows = ring_spans(run)
    recs = run.traced_records()
    if not rows or not recs or run.trace_data is None:
        return None
    lo = min(r["t_start"] for r in recs) * 1e9
    hi = max(r["t_end"] for r in recs) * 1e9
    inside = [r for r in rows if r[3] >= lo and r[4] <= hi]
    children = {}
    for sid, parent, _, start, end, _ in inside:
        children[parent] = children.get(parent, 0) + (end - start)
    own = [(end - start) - children.get(sid, 0)
           for sid, _, name, start, end, _ in inside
           if name.startswith(("entry.", "sampler."))]
    return sum(own) / len(recs) / 1e6 if own else None


def spans_on_trace_clock(run):
    """The program's spans as ``(name, start, dur)`` on the trace's clock,
    through the benchmark's ``call`` span, which is on both: the harness
    timed it with ``time.monotonic()`` and the profiler recorded it."""
    rows = ring_spans(run)
    calls = [s for s in run.trace_data.spans if s[0] == T.SPAN_PREFIX + "call"]
    timed = [r for r in run.spans.rows if r[0] == "call"
             and run.traced[0] <= r[1] <= run.traced[1]]
    if not rows or not calls or not timed:
        return []
    offset = calls[0][1] - timed[0][1] * 1e9
    return [(name, start + offset, end - start)
            for _, _, name, start, end, _ in rows]


# -- the program's compile ledger ------------------------------------------

def ledger_rows(run, *kinds):
    """The program's compile-ledger rows of ``kinds`` that ended during
    set-up; None where the program keeps no ledger (before PR 27) or the run
    is not on the chip (the toy presets compile other programs, and the
    rehearsal's result line is pinned to the metrics it had)."""
    if not run.on_chip:
        return None
    try:
        from p2p_tpu.utils.cache import compile_ledger
    except ImportError:
        return None
    return compile_ledger().rows(*kinds, since=run.t_process,
                                 before=run.t_setup_done)


# -- the tree, for PERF.md -------------------------------------------------

def tree(scoped: Scoped, loop: bool):
    """``{path prefix: [ns, ambiguous ns, relayout ns]}`` over every prefix
    of every row's scope, for the loop or for what lies outside it."""
    agg = {}
    for r in scoped.rows:
        if r.op.loop != loop:
            continue
        parts = r.scope.split("/") if r.scope else ["(no scope)"]
        for depth in range(1, len(parts) + 1):
            cell = agg.setdefault("/".join(parts[:depth]), [0.0, 0.0, 0.0])
            cell[0] += r.op.dur
            cell[1] += r.op.dur * r.ambiguous
            cell[2] += r.op.dur * (T.op_class(r.op) == "relayout")
    return agg


def _print(run, scoped: Scoped, indexes, rss_mib: float) -> None:
    say = lambda *a: print(*a, file=sys.stderr)          # noqa: E731
    n = sum(len(ix[0]) for ix in indexes.values() if ix)
    how, late = {}, None
    try:                        # what the program says about building them
        from p2p_tpu.obs import launches
        from p2p_tpu.utils.cache import compile_ledger

        how = {p.module: f"{p.built_from}, newest of {len(launches.programs(p.module))}"
               for p in launches.programs() if p.index is not None}
        late = compile_ledger().rows("backend", "cache_hit", since=run.t_setup_done)
    except ImportError:
        pass
    say(f"scope index: {n} instructions of {sorted(m for m, ix in indexes.items() if ix)}"
        f" in {scoped.build_s:.2f} s ({how}), peak RSS +{rss_mib:.0f} MiB;"
        f" scoped {scoped.scoped_pct:.2f} % of the window's device time")
    if late is not None:
        say("programs built after set-up (kind seconds name): " + (" ".join(
            f"{r.kind}:{r.seconds:.2f}:{r.name}" for r in late) or "none"))
    for loop, unit, per in ((True, "ms/step", scoped.steps),
                            (False, "ms/call", scoped.calls)):
        agg = tree(scoped, loop)
        total = sum(r.op.dur for r in scoped.rows if r.op.loop == loop)
        if not total or not per:
            continue
        amb = sum(r.op.dur for r in scoped.rows if r.op.loop == loop and r.ambiguous)
        crs = sum(r.op.dur for r in scoped.rows
                  if r.op.loop == loop and r.crosses_parts)
        say(f"scope tree, {'loop' if loop else 'outside the loop'}: "
            f"{total / scoped.ndev / per / 1e6:.3f} {unit}; in fusions that span "
            f"second-level scopes {100 * amb / total:.2f} %, that span two of "
            f"the parts {100 * crs / total:.2f} %")
        say(f"  {'scope':<58}{unit:>9} {'share%':>7} {'ambig%':>7} {'relay%':>7}")
        for path in sorted(agg):
            ns, ambiguous, relayout = agg[path]
            say(f"  {'  ' * path.count('/') + path.rsplit('/', 1)[-1]:<58}"
                f"{ns / scoped.ndev / per / 1e6:9.3f} {100 * ns / total:7.2f} "
                f"{100 * ambiguous / (ns or 1):7.2f} {100 * relayout / (ns or 1):7.2f}")
    if scoped.scoped_pct >= SCOPED_FLOOR_PCT:
        say("step by part (ms/step): " + " ".join(
            f"{p}:{scoped.loop_ms_per_step(p):.3f}" for p in PARTS))
    spans = spans_on_trace_clock(run)
    if spans:
        lo, hi = run.trace_window
        gaps = T.idle_gaps(SimpleNamespace(devices=run.trace_data.devices,
                                           spans=spans), lo, hi)
        say("device idle by the program's innermost open span (ms/call): "
            + " ".join(f"{k}:{v * 1e3 / scoped.calls:.3f}" for k, v in gaps))
