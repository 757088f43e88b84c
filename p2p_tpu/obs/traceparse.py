"""traceparse — shared chrome-trace / workload-profile parsing (ISSUE 18).

The ``perfscope --sites`` named_scope parser, factored out of the CLI so
the serve engine's production profiler (:mod:`p2p_tpu.obs.prodscope`) and
the tools (``perfscope``, ``schedule_search``) fold traces through one
code path. Three layers:

- **Chrome-trace loading** (:func:`load_trace_events`,
  :func:`parse_site_trace`): gz-aware ``traceEvents`` extraction and the
  PR-15 per-attention-site duration fold, behavior-identical to the old
  ``tools/perfscope.py`` implementation.
- **HLO op→scope indexing** (:func:`scope_index`, its attention-site
  form :func:`op_site_index`, :func:`fold_site_events`): a TPU trace
  names a device event by its HLO instruction and a CPU trace carries
  ``args.hlo_op`` — neither carries the ``named_scope`` path. But the
  *compiled HLO text* keeps the full scope path in per-instruction
  ``metadata={op_name="..."}``. Indexing instruction names to scopes
  (fusions attributed to the dominant scope of their called computation)
  lets a reader recover measured per-scope durations from traces whose
  event names alone carry no scope information
  (``obs.launches.scope_index`` offers the index of a program that ran).
- **WorkloadProfile format** (:data:`PROFILE_FORMAT`,
  :func:`is_workload_profile`, :func:`load_workload_profile`,
  :func:`profile_sites`, :func:`validate_profile`): the durable ledger
  the profiler writes and ``schedule_search --profile`` /
  ``perfscope --sites`` consume. Format confusion (a ledger where a
  trace was expected, or vice versa) is a loud ``ValueError`` naming
  both formats — never a silent empty table.

Stdlib-only on purpose: tools import it without pulling jax.
"""

from __future__ import annotations

import gzip
import json
import re
from collections import Counter
from typing import Dict, List, Optional, Tuple

#: Format sentinel every WorkloadProfile ledger carries under ``format``.
PROFILE_FORMAT = "p2p-workload-profile/v1"

#: An attention site name as it appears inside named_scope paths and HLO
#: op metadata: ``cross_attn/down3``, ``self_attn/mid0``, ...
SITE_RE = re.compile(r"(cross_attn|self_attn)/(?:down|mid|up|block)\d+")

_PLACE = r"(?:down|mid|up)\d+"
#: The program's whole scope vocabulary (docs/OBSERVABILITY.md, "Scope
#: vocabulary"), outermost first; the longest documented prefix of a path
#: matches, so whatever JAX appends below a scope (primitive names, inner
#: function names) is dropped.
SCOPE_RE = re.compile(
    r"(?<![\w.])(?:text_encoder(?:/(?:tower\d+|pool|t5(?:/layer\d+)?))?"
    r"|sampler/(?:cfg|scheduler_step|controller_step)"
    r"|unet(?:/(?:time_embed|add_embed|conv_in|conv_out"
    rf"|{_PLACE}(?:/(?:res\d+|downsample|upsample|skip_concat"
    r"|attn\d+(?:/(?:proj_in|proj_out|ff"
    rf"|(?:self_attn|cross_attn)/{_PLACE}(?:/(?:qkv|core|out))?))?))?))?"
    r"|dit(?:/(?:patch_embed|time_embed|caption_proj|final"
    r"|block\d+(?:/(?:ff|modulate"
    r"|(?:self_attn|cross_attn)/block\d+(?:/(?:qkv|core|out))?))?))?"
    r"|vae\.decode(?:/(?:conv_in|mid|up\d+|conv_out))?)(?![\w.])")

# HLO-text structure: a computation header opens a ``{`` block (its
# parameter list may nest parentheses: a TPU layout reads ``{1,0:T(8,128)}``), each
# instruction line is ``%name = <result> opcode(...)`` and may carry
# ``metadata={op_name="scope/path" ...}``, and fusion instructions name
# their called computation via ``calls=``.
_COMP_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s*\(.*->.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s.*?\s([a-z][a-z\-]*)\(")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]+)"')
_CALLS_RE = re.compile(r"calls=%?([A-Za-z0-9_.\-]+)")
# What JAX puts into an ``op_name`` around the user's scopes: transform
# wrappers (``vmap(unet)/down0`` wraps the first scope below it) and the
# components a ``lax.scan`` / ``while_loop`` inside a scope adds.
_WRAPPER_RE = re.compile(r"[A-Za-z_]+\(([^()]*)\)")
_LOOP_PARTS_RE = re.compile(r"/(?:while|body|cond|closed_call)(?=/|$)")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_.\-]+)")
#: A fusion with members of these opcodes is that member's scope's on a tie.
_HEAVY_OPCODES = ("convolution", "dot", "custom-call")
#: Opcodes that read what many scopes made: a scope is not found through them.
_AGGREGATES = ("tuple", "while", "call", "conditional")
#: How far from its reader a compiler-made instruction may stand.
_HOPS = 4
#: Opcodes that take no time on a device: they need no scope.
_NEVER_RUN = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")

#: Top-level keys a v1 ledger must carry (schema table in
#: docs/OBSERVABILITY.md mirrors this).
PROFILE_REQUIRED_KEYS = (
    "format", "version", "tags", "window", "captures", "sites",
    "programs", "phases", "kernels", "schedule_segments",
    "stage_histograms", "device_memory", "drift", "overhead",
)


def _load_json(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def load_trace_events(path: str) -> list:
    """Chrome-trace events from ``path`` (``traceEvents`` object or bare
    event list, ``.gz``-compressed or not). Loud on format confusion:
    handing it a WorkloadProfile ledger is a ``ValueError`` naming the
    right flag, never an empty fold."""
    data = _load_json(path)
    if isinstance(data, dict) and is_workload_profile(data):
        raise ValueError(
            f"{path}: this is a WorkloadProfile ledger "
            f"({PROFILE_FORMAT}), not a chrome trace — pass it where a "
            "profile is accepted (perfscope --sites auto-detects it; "
            "schedule_search takes --profile)")
    events = data.get("traceEvents", data) if isinstance(data, dict) \
        else data
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a chrome-trace (no traceEvents "
                         "list)")
    return events


def fold_site_events(events: list, op_index: Optional[Dict[str, str]]
                     = None) -> list:
    """Sum per-site durations over chrome-trace ``events``.

    Sites are resolved from the event name via :data:`SITE_RE`
    (CPU thunk names carry the named_scope path), falling back to
    ``op_index`` — an ``{hlo instruction name: site}`` map built by
    :func:`op_site_index` — keyed by ``args.hlo_op`` (or the bare event
    name) for backends whose trace events carry only HLO op names.
    Returns ``[{"site", "dur_us", "slices", "share"}]`` sorted hottest
    first; empty when nothing matched (callers decide how loud that is).
    """
    durs: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for e in events:
        if not isinstance(e, dict):
            continue
        name = e.get("name")
        dur = e.get("dur")
        if not name or dur is None:
            continue
        site = None
        m = SITE_RE.search(str(name))
        if m:
            site = m.group(0)
        elif op_index:
            args = e.get("args") or {}
            op = args.get("hlo_op") or name
            site = op_index.get(str(op))
        if site is None:
            continue
        durs[site] = durs.get(site, 0.0) + float(dur)
        counts[site] = counts.get(site, 0) + 1
    total = sum(durs.values())
    return [{"site": s, "dur_us": durs[s], "slices": counts[s],
             "share": (durs[s] / total) if total else 0.0}
            for s in sorted(durs, key=lambda s: -durs[s])]


def parse_site_trace(path: str, op_index: Optional[Dict[str, str]]
                     = None) -> list:
    """Aggregate per-attention-site device time from a Perfetto/Chrome
    trace (ISSUE 15, the schedule search's seed input).

    Every attention site is wrapped in a ``jax.named_scope`` whose name
    (``cross_attn/down3``) lands in the HLO op metadata. CPU thunk names
    carry that path, so their slices match by name; a TPU trace names a
    slice by its HLO instruction alone, and ``op_index`` (see
    :func:`op_site_index`) recovers the site from the compiled program's
    text. Durations are summed per site, shares normalized over
    all matched sites. Raises ``ValueError`` when no site slice matched
    — and, loudly, when handed a WorkloadProfile ledger instead of a
    trace."""
    entries = fold_site_events(load_trace_events(path), op_index)
    if not entries:
        raise ValueError(
            f"{path}: no attention-site slices found — is this a DEVICE "
            "trace of a named_scope-instrumented program? (site names "
            "look like 'cross_attn/down3')")
    return entries


def scope_of(op_name: str, pattern: "re.Pattern" = SCOPE_RE) -> Optional[str]:
    """The scope ``pattern`` finds in an instruction's ``op_name``, with
    JAX's transform wrappers and loop components taken out first
    (``jit(f)/vmap(unet)/down0/while/body/closed_call/res0/sin`` reads
    ``unet/down0/res0``); None where it finds none."""
    prev = None
    while prev != op_name:
        prev, op_name = op_name, _WRAPPER_RE.sub(r"\1", op_name)
    m = pattern.search(_LOOP_PARTS_RE.sub("", op_name))
    return m.group(0) if m else None


def scope_index(hlo_text: str, pattern: "re.Pattern" = SCOPE_RE
                ) -> Tuple[Dict[str, str], Dict[str, Dict[str, int]]]:
    """``({HLO instruction name: scope}, {fusion name: {scope: members}})``
    from compiled HLO text: the join key between a device trace, whose
    events are named by instruction, and the program's ``named_scope``s.

    An instruction's scope is what ``pattern`` finds in its
    ``metadata.op_name`` (:func:`scope_of`). A fusion's own metadata names
    only one member op, so a fusion goes to the *dominant* scope of its
    called computation — the scope owning the most member instructions,
    a tie going to the scope that owns a convolution, dot or custom call —
    and to its own metadata only where no member has a scope. An
    instruction that has metadata and no scope in it has none: it is left
    out. One the compiler added *without any metadata* (on the TPU a
    weight's asynchronous copy) takes the scope of the nearest instruction
    that reads its result (:func:`_scope_of_users`). The second dict holds
    the fusions whose members lie in more than one scope, with the member
    count of each, so a reader can say how much device time is attributed
    across a scope boundary."""
    index: Dict[str, str] = {}
    members: Dict[str, Counter] = {}     # computation -> scope -> members
    heavy: Dict[str, set] = {}           # computation -> scopes of heavy ops
    fusions: List[Tuple[str, str]] = []
    bare: Dict[str, str] = {}            # no metadata at all: opcode
    users: Dict[str, List[str]] = {}     # instruction -> the ones that read it
    current = None
    for line in hlo_text.splitlines():
        im = _INSTR_RE.match(line)
        if im is None:
            cm = _COMP_RE.match(line)
            if cm:
                current = cm.group(1)
            continue
        name, opcode = im.groups()
        om = _OP_NAME_RE.search(line)
        scope = scope_of(om.group(1), pattern) if om else None
        if scope is not None:
            index[name] = scope
            if current is not None:
                members.setdefault(current, Counter())[scope] += 1
                if opcode in _HEAVY_OPCODES:
                    heavy.setdefault(current, set()).add(scope)
        elif om is None:
            bare[name] = opcode
        if opcode == "fusion":
            fm = _CALLS_RE.search(line)
            if fm:
                fusions.append((name, fm.group(1)))
        for operand in _OPERAND_RE.findall(line, im.end()):
            users.setdefault(operand, []).append(name)
    mixed: Dict[str, Dict[str, int]] = {}
    for name, comp in fusions:
        ctr = members.get(comp)
        if not ctr:
            continue
        top = max(ctr.values())
        tied = [s for s, n in ctr.items() if n == top]
        index[name] = next((s for s in tied if s in heavy.get(comp, ())),
                           tied[0])
        if len(ctr) > 1:
            mixed[name] = dict(ctr)
    for name, opcode in bare.items():
        if name not in index and opcode not in _NEVER_RUN:
            scope = _scope_of_users(name, users, index, bare)
            if scope is not None:
                index[name] = scope
    return index, mixed


def _scope_of_users(name: str, users, index, bare) -> Optional[str]:
    """The scope of the nearest instruction that reads ``name``'s result,
    through at most ``_HOPS`` instructions that have no metadata either:
    what the compiler adds in front of a scoped operation (on the TPU the
    asynchronous copies and slices that bring a layer's weights near, the
    ``ConcatBitcast`` that joins them) works for that operation. Not through
    an instruction that has metadata and no scope (its work is its own), nor
    through a tuple or a loop, which read everything."""
    frontier = [name]
    for _ in range(_HOPS):
        frontier = [u for n in frontier for u in users.get(n, ())]
        for u in frontier:
            if u in index:
                return index[u]
        frontier = [u for u in frontier
                    if u in bare and bare[u] not in _AGGREGATES]
    return None


def op_site_index(hlo_text: str) -> Dict[str, str]:
    """``{HLO instruction name: attention site}`` from compiled HLO text:
    :func:`scope_index` with :data:`SITE_RE` as the scope pattern, so a
    fusion goes to the site owning the most member instructions, and an
    instruction outside every site (``proj_in``, a ResNet block) is in no
    site's time whatever reads it. This is the join key that makes CPU
    traces (bare ``dot.596`` event names, ``args.hlo_op``) yield measured
    per-site shares."""
    return scope_index(hlo_text, SITE_RE)[0]


# -- WorkloadProfile format ----------------------------------------------


def is_workload_profile(doc) -> bool:
    return (isinstance(doc, dict)
            and doc.get("format") == PROFILE_FORMAT)


def load_workload_profile(path: str) -> dict:
    """A WorkloadProfile ledger from ``path``, loud on confusion: a
    chrome trace (or anything else) raises ``ValueError`` naming what was
    found and what was expected."""
    doc = _load_json(path)
    if isinstance(doc, dict) and not is_workload_profile(doc) \
            and isinstance(doc.get("traceEvents"), list):
        raise ValueError(
            f"{path}: this is a chrome trace, not a WorkloadProfile "
            f"ledger ({PROFILE_FORMAT}) — pass it where a trace is "
            "accepted (perfscope --sites TRACE, or fold it with "
            "serve --profile first)")
    if not is_workload_profile(doc):
        raise ValueError(
            f"{path}: not a WorkloadProfile ledger — expected a JSON "
            f"object with format={PROFILE_FORMAT!r}, got "
            f"{type(doc).__name__} with format="
            f"{doc.get('format')!r}" if isinstance(doc, dict) else
            f"{path}: not a WorkloadProfile ledger — expected a JSON "
            f"object with format={PROFILE_FORMAT!r}")
    return doc


def profile_sites(doc: dict) -> list:
    """The ledger's per-site table in the exact ``--sites-json`` /
    ``parse_site_trace`` entry shape. Loud when the ledger carries no
    measured sites (a profile captured before any dispatch folded)."""
    sites = doc.get("sites")
    if not isinstance(sites, list) or not sites:
        raise ValueError(
            "workload profile carries no measured sites — was any "
            "dispatch sampled? (captures: "
            f"{(doc.get('captures') or {}).get('count', 0)})")
    bad = [e for e in sites
           if not isinstance(e, dict) or "site" not in e
           or "share" not in e]
    if bad:
        raise ValueError(f"workload profile sites entries malformed: "
                         f"{bad[:2]!r}")
    return sites


def validate_profile(doc: dict) -> List[str]:
    """Schema problems in a ledger, empty when valid (the quality-gate
    ``profile_parity`` leg's validation unit)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not an object: {type(doc).__name__}"]
    if doc.get("format") != PROFILE_FORMAT:
        problems.append(f"format is {doc.get('format')!r}, "
                        f"expected {PROFILE_FORMAT!r}")
    for key in PROFILE_REQUIRED_KEYS:
        if key not in doc:
            problems.append(f"missing key {key!r}")
    sites = doc.get("sites")
    if isinstance(sites, list):
        for e in sites:
            if not isinstance(e, dict) or not {"site", "dur_us",
                                               "slices", "share"} <= set(e):
                problems.append(f"malformed sites entry: {e!r}")
                break
        total = sum(float(e.get("share", 0.0)) for e in sites
                    if isinstance(e, dict))
        if sites and not (0.999 <= total <= 1.001):
            problems.append(f"site shares sum to {total:.4f}, not 1")
    elif "sites" in doc:
        problems.append("sites is not a list")
    progs = doc.get("programs")
    if isinstance(progs, list):
        for p in progs:
            if not isinstance(p, dict) or "program" not in p:
                problems.append(f"malformed programs entry: {p!r}")
                break
    over = doc.get("overhead")
    if isinstance(over, dict):
        pct = over.get("overhead_pct")
        if pct is not None and (not isinstance(pct, (int, float))
                                or pct < 0):
            problems.append(f"overhead_pct invalid: {pct!r}")
    return problems


def parse_sites_any(path: str) -> Tuple[list, str]:
    """Site entries from either a chrome trace or a WorkloadProfile
    ledger — sniffed by content, with each format's loud errors intact.
    Returns ``(entries, kind)`` with kind ``"trace"`` or ``"profile"``.
    """
    doc = _load_json(path)
    if is_workload_profile(doc):
        return profile_sites(doc), "profile"
    return parse_site_trace(path), "trace"
