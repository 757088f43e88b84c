"""Text walkers over lowered mesh programs — shardcheck's vocabulary.

The jaxpr walkers (:mod:`.jaxpr_walk`) see the program *before* XLA does;
this module reads what XLA actually emits, at two stages:

- **Lowered StableHLO** (``jitted.lower(...).as_text()``) — where sharding
  *intent* lives, in the Shardy (``sdy``) dialect: ``sdy.sharding_constraint``
  (a ``with_sharding_constraint``), ``sdy.manual_computation`` (a
  ``shard_map`` boundary) and host-callback custom calls. A resharding op
  in a canonical dp program is someone *asking* for data movement the dp
  design promises not to need.
- **Compiled post-SPMD HLO** (``.compile().as_text()``) — where sharding
  *consequence* lives: after the partitioner runs, every implicit
  reshard has become a real collective (``all-reduce`` / ``all-gather`` /
  ``all-to-all`` / ``collective-permute`` / ``collective-broadcast``) with
  a concrete dtype, shape and replica grouping. This is the ground truth
  the declared-collective contract (:mod:`.collectives`) checks against —
  the compile-time twin of the runtime ``jax.transfer_guard`` tests.

Everything here is string parsing over the textual forms the installed
jax emits — deliberately: no MLIR bindings, no XLA internals, and the parsed
shapes are cross-checked by seeded-violation tests
(tests/test_shardcheck.py) so a silent format drift breaks loudly.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

#: Collective op mnemonics as the post-partitioning HLO text spells them.
#: ``reduce-scatter`` matters even on a dp-only mesh: XLA rewrites an
#: all-reduce whose consumer is sharded into reduce-scatter, so omitting
#: it would blind the check to a whole class of partitioner-inserted
#: traffic. Async spellings (``all-gather-start``/``-done``) are folded
#: onto their sync kind — the ``-start`` op carries the traffic, the
#: ``-done`` is a wait and is skipped.
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute",
                    "collective-broadcast")

#: HLO element-type byte widths (tuple/token types are handled structurally).
DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_TENSOR_TYPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"%\S+\s*=\s*(?P<type>[^=]*?)\s*"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")"
    r"(?P<start>-start)?(?:\.\d+)?\(")
# replica_groups={{0,1},{2,3}} (explicit), replica_groups=[2,2]<=[4]
# (iota), or replica_groups={} (ONE group of all partitions — sized from
# the HloModule header's num_partitions). collective-permute carries
# source_target_pairs instead; any non-self pair means real traffic.
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_EMPTY_RE = re.compile(r"replica_groups=\{\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[0-9,{} ]*\})\}")
_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)")
# Computation headers carry nested parens for tuple-typed params
# (`%body (p: (s32[], f32[])) -> ...`), so the param blob is matched
# greedily; the `) -> ... {` tail anchors the header shape.
_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{$")
# Callee references: single-name attrs (`calls=%f`, `body=%b`) and brace
# lists (`branch_computations={%b0, %b1}` — every member counts, or a
# collective in a later conditional branch would lose its per-step
# attribution).
_CALLED_ONE_RE = re.compile(r"(?:calls|to_apply|body|condition|"
                            r"true_computation|false_computation)=%?"
                            r"([\w.\-]+)")
_CALLED_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE_BODY_RE = re.compile(r"\bwhile\(.*body=%?([\w.\-]+)")


@dataclasses.dataclass
class CollectiveOp:
    """One collective in a compiled (post-SPMD) HLO module."""

    kind: str                 # one of COLLECTIVE_KINDS
    dtype: str                # element type of the (first) payload tensor
    shape: Tuple[int, ...]    # payload tensor shape
    payload_bytes: int        # sum over all result tensors
    group_size: int           # devices per replica group (1 = degenerate)
    per_step: bool            # inside a while (scan) body → paid every step
    computation: str          # HLO computation holding the op
    line: str                 # the (trimmed) HLO line, for error messages

    @property
    def bytes_moved(self) -> int:
        return cost_bytes(self.kind, self.payload_bytes, self.group_size)

    def describe(self) -> str:
        where = "per-step" if self.per_step else "once"
        return (f"{self.kind} {self.dtype}{list(self.shape)} "
                f"group={self.group_size} ~{self.bytes_moved}B {where}")


def cost_bytes(kind: str, payload_bytes: int, group_size: int) -> int:
    """Bytes each participant moves over the interconnect for one op — the
    standard ring-algorithm counts, the budget unit the comms table (and
    the upcoming mp-axis PR) is denominated in:

    - ``all-gather`` / ``all-to-all``: ``(g-1)/g`` of the full payload
      (every shard but your own crosses the wire).
    - ``all-reduce``: ``2(g-1)/g`` (reduce-scatter + all-gather phases).
    - ``reduce-scatter``: ``(g-1)``× the payload — the HLO result type is
      the *shard*, and each participant sends every shard but its own.
    - ``collective-permute`` / ``collective-broadcast``: the full payload
      (one explicit hop).

    A degenerate group (``g == 1``) moves nothing — dp=1 programs cost 0
    by construction, which is what keeps the dp=1 leg a real (non-vacuous)
    baseline row rather than a skipped one.
    """
    if group_size <= 1:
        return 0
    frac = (group_size - 1) / group_size
    if kind == "all-reduce":
        return int(2 * frac * payload_bytes)
    if kind == "reduce-scatter":
        return (group_size - 1) * payload_bytes
    if kind in ("all-gather", "all-to-all"):
        return int(frac * payload_bytes)
    return payload_bytes


def _parse_types(type_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Tensor (dtype, shape) list from an HLO result-type string —
    ``f32[4,8]{1,0}`` or a tuple ``(f32[4], u32[])``. Layout suffixes and
    ``token[]`` pseudo-types are ignored."""
    out = []
    for dtype, dims in _TENSOR_TYPE_RE.findall(type_text):
        if dtype not in DTYPE_BYTES:
            continue   # token[], opaque[] — no payload
        shape = tuple(int(d) for d in dims.split(",") if d != "")
        out.append((dtype, shape))
    return out


def _numel(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _group_size(line: str, num_partitions: int = 1) -> int:
    """Effective replica-group size for one collective line. Degenerate
    (size-1) groups price to 0 in :func:`cost_bytes`, so every spelling
    that means "real traffic" must resolve to > 1 here: an empty
    ``replica_groups={}`` is ONE group of all ``num_partitions`` devices,
    and a ``collective-permute`` has no groups at all — any pair whose
    source differs from its target moves the full payload."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # [G,S]<=[N]: G groups of S devices each.
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        first = m.group(1).split("}")[0]
        return len([t for t in first.split(",") if t.strip() != ""])
    if _GROUPS_EMPTY_RE.search(line):
        return max(num_partitions, 1)
    m = _PAIRS_RE.search(line)
    if m:
        pairs = re.findall(r"\{\s*(\d+)\s*,\s*(\d+)\s*\}", m.group(0))
        moving = any(a != b for a, b in pairs)
        return 2 if moving else 1
    return 1


def _computation_spans(hlo_text: str) -> List[Tuple[str, List[str]]]:
    """(computation name, its lines) for every computation in an HLO
    module, in file order. HLO text opens a computation with
    ``[ENTRY] %name (params) -> type {`` at top level."""
    spans: List[Tuple[str, List[str]]] = []
    current: Optional[str] = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _COMPUTATION_RE.match(line)
        if m:
            current = m.group(1)
            spans.append((current, []))
            continue
        if current is not None:
            spans[-1][1].append(line)
            if line == "}":
                current = None
    return spans


def _per_step_computations(spans: List[Tuple[str, List[str]]]) -> set:
    """Names of computations executed once per while-loop (scan) iteration:
    every while body plus the transitive closure of computations it calls
    (fusions via ``calls=``, reducers via ``to_apply=``, nested control
    flow via ``body=``/``condition=``)."""
    called: Dict[str, set] = {}
    bodies: set = set()
    for name, lines in spans:
        refs = set()
        for line in lines:
            refs.update(_CALLED_ONE_RE.findall(line))
            for blob in _CALLED_LIST_RE.findall(line):
                refs.update(t.strip().lstrip("%")
                            for t in blob.split(",") if t.strip())
            wb = _WHILE_BODY_RE.search(line)
            if wb:
                bodies.add(wb.group(1))
        called[name] = refs
    per_step = set()
    frontier = list(bodies)
    while frontier:
        name = frontier.pop()
        if name in per_step:
            continue
        per_step.add(name)
        frontier.extend(called.get(name, ()))
    return per_step


def collective_ops(hlo_text: str) -> List[CollectiveOp]:
    """Every collective in a compiled HLO module, with its payload cost and
    whether it sits inside a scan (while) body."""
    spans = _computation_spans(hlo_text)
    per_step = _per_step_computations(spans)
    np_m = _NUM_PARTITIONS_RE.search(hlo_text[:2000])   # HloModule header
    num_partitions = int(np_m.group(1)) if np_m else 1
    ops: List[CollectiveOp] = []
    for comp_name, lines in spans:
        for line in lines:
            m = _COLLECTIVE_RE.search(line)
            if not m:
                continue
            types = _parse_types(m.group("type"))
            if m.group("start") and len(types) > 1:
                # Async form: the result tuple aliases operands and may
                # trail context words (permute-start's u32[] pair); the
                # transferred payload is the LARGEST element, not the
                # last or the sum.
                types = [max(types,
                             key=lambda t: DTYPE_BYTES[t[0]] * _numel(t[1]))]
            payload = sum(DTYPE_BYTES[dt] * _numel(sh) for dt, sh in types)
            dtype, shape = types[0] if types else ("?", ())
            ops.append(CollectiveOp(
                kind=m.group("kind"), dtype=dtype, shape=shape,
                payload_bytes=payload,
                group_size=_group_size(line, num_partitions),
                per_step=comp_name in per_step, computation=comp_name,
                line=line[:160]))
    return ops


def collective_signature(ops: List[CollectiveOp]) -> dict:
    """The per-program comms summary the report JSON carries: an op-kind
    multiset plus the bytes-per-step / bytes-once split of the ring-cost
    model — the budget the mp-axis work designs against."""
    kinds: Dict[str, int] = {}
    per_step = once = 0
    for op in ops:
        kinds[op.kind] = kinds.get(op.kind, 0) + 1
        if op.per_step:
            per_step += op.bytes_moved
        else:
            once += op.bytes_moved
    return {"ops": dict(sorted(kinds.items())),
            "bytes_per_step": per_step, "bytes_once": once}


# ---------------------------------------------------------------------------
# StableHLO-side detectors (pre-partitioning intent)
# ---------------------------------------------------------------------------

# `%2 = sdy.sharding_constraint %1 <@mesh, [{"dp"}, {}]> : tensor<4x8xf32>`
# (also sdy.reshard, same spelling), and the shard_map boundary
# `sdy.manual_computation(...) in_shardings=[...] out_shardings=[...]
# manual_axes={...} (...) {` whose result type closes the region.
_SDY_CONSTRAINT_RE = re.compile(
    r"sdy\.(sharding_constraint|reshard)\s+%\S+\s+<@[\w.]+,\s*"
    r"(\[[^\n]*?\])(?:,[^>\n]*)?>\s*:\s*tensor<([^>]*)>")
_SDY_MANUAL_RE = re.compile(
    r"sdy\.(manual_computation)\([^\n]*?out_shardings=(\[[^\n]*?\])\s*"
    r"manual_axes=")
_SDY_AXIS_RE = re.compile(r'"[^"]+"')


@dataclasses.dataclass
class ShardingChange:
    """One sharding-changing op in lowered StableHLO (``sdy`` dialect)."""

    target: str        # sharding_constraint | reshard | manual_computation
    sharding: str      # the per-dimension axis list, e.g. '[{"dp"}, {}]'
    result_type: str   # e.g. "4x8x8x16xf32" ("?" for a manual computation)

    def describe(self) -> str:
        return (f"sdy.{self.target} -> tensor<{self.result_type}> "
                f"sharding={self.sharding or '?'}")

    @property
    def forces_replication(self) -> bool:
        """A mid-program constraint that replicates a value — the "silent
        full replication of a dp-sharded tensor" shape of the bug: a
        constraint whose every dimension names no mesh axis."""
        return (self.target != "manual_computation"
                and not _SDY_AXIS_RE.search(self.sharding))


def sharding_changes(stablehlo_text: str) -> List[ShardingChange]:
    """All sharding-changing ops in a lowered StableHLO module.
    Input-argument shardings (``sdy.sharding`` on the entry params) are
    NOT included: staging inputs under a NamedSharding is the declared
    dispatch contract, not a mid-program reshard."""
    out = [ShardingChange(m.group(1), m.group(2), m.group(3))
           for m in _SDY_CONSTRAINT_RE.finditer(stablehlo_text)]
    out += [ShardingChange(m.group(1), m.group(2), "?")
            for m in _SDY_MANUAL_RE.finditer(stablehlo_text)]
    return out


# ---------------------------------------------------------------------------
# Host-boundary ops (either text form)
# ---------------------------------------------------------------------------

_HOST_HLO_RE = re.compile(
    r'custom-call[^\n]*custom_call_target="([^"]*callback[^"]*)"')
_HOST_SHLO_RE = re.compile(
    r'stablehlo\.custom_call\s+@([\w.]*callback[\w.]*)')


def host_boundary_ops(text: str) -> List[str]:
    """Host-crossing ops in either a StableHLO or a compiled HLO module:
    the host-callback custom calls ``io_callback`` / ``jax.debug.callback``
    / ``pure_callback`` lower to. Each entry names the callback target."""
    out = [f"custom-call:{m.group(1)}" for m in _HOST_HLO_RE.finditer(text)]
    out += [f"custom_call:@{m.group(1)}"
            for m in _HOST_SHLO_RE.finditer(text)]
    return out
