"""hlo_costs — what a compiled program moves between its heavy operations.

Given the text of a compiled program (``compiled.as_text()`` of a
``jax.jit(f).lower(...).compile()``, on the chip or for a *described* v5e as
``tests/test_chip_compile.py`` makes them in the sandbox), list by program
scope the scheduled ``copy`` / ``broadcast`` / ``pad`` / ``transpose``
instructions whose result is at least ``--min-mb`` (1 MB), each with its
bytes, the layout it reads and the layout it writes and XLA's
``estimated_cycles``, and the totals of every scheduled instruction by
opcode:

    python tools/hlo_costs.py program.hlo.txt
    python tools/hlo_costs.py program.hlo.txt --computation 'body' --min-mb 8
    python tools/hlo_costs.py program.hlo.txt --json out.json

**The cycles are XLA's cost model, not a time.** ``estimated_cycles`` is what
the TPU compiler's scheduler assumed for the instruction
(``backend_config.window_config``); nothing ran. It says which operations the
compiler put between two convolutions and how it weighs them against each
other, so two forms of one computation can be compared before any chip time
is spent; what either costs is read off a device trace
(``benchmarks/run.py --trace 1``). PERF.md §6 (PR 32) has one comparison:
the model put that PR's change at 0.946 / 0.971 of the two cells' loops, the
chip at 0.918 / 0.962 of one U-Net forward.

A *scheduled* instruction is one the compiler gave a cost: the members of a
fusion have none, the fusion has. A fusion is counted under the heaviest
opcode among its members (``convolution``, ``dot``, ``reduce``), under the
one data-movement opcode it wraps if it holds nothing else, else as
``fusion``. Scopes are the program's ``jax.named_scope`` paths as the device
trace's reader finds them (``p2p_tpu.obs.traceparse.scope_index``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
from collections import Counter
from typing import Dict, List, NamedTuple, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from p2p_tpu.obs import traceparse  # noqa: E402  (the HLO text's grammar)

#: The data-movement opcodes the listing is about.
MOVES = ("copy", "broadcast", "pad", "transpose")
#: A fusion that holds one of these is that operation's, heaviest first.
_HEAVY = ("convolution", "dot", "reduce", "reduce-window")
#: Members that do no work of their own inside a fusion.
_FREE = ("parameter", "constant", "bitcast", "tuple", "get-tuple-element",
         "reshape")

# traceparse's instruction pattern with the result type kept as well
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*(.*?)\s([a-z][a-z\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\](?:\{([0-9,]*))?")
_CYCLES_RE = re.compile(r'"estimated_cycles":"(\d+)"')
_BITS_RE = re.compile(r"(\d+)$")


class Instr(NamedTuple):
    name: str
    computation: str
    opcode: str          # the instruction's own
    kind: str            # what it is counted under (see the module docstring)
    shape: str           # ``f32[4,64,64,320]``; a tuple's first array
    layout: str          # ``{2,1,3,0}``, minor dimension first; '' if none
    nbytes: int          # of the result's logical shape (no tile padding)
    cycles: int
    reads: str           # layout of the first array operand; '' if unknown
    scope: str


def _first_array(result: str):
    """``(shape text, layout text, bytes)`` of the first array in a result
    type (a tuple's first member stands for it)."""
    m = _SHAPE_RE.search(result)
    if m is None:
        return "", "", 0
    dtype, dims, layout = m.groups()
    bits = 8 if dtype == "pred" else int(_BITS_RE.search(dtype).group(1))
    n = math.prod(int(d) for d in dims.split(",") if d)
    return (f"{dtype}[{dims}]", "{%s}" % layout if layout else "",
            n * max(bits // 8, 1))


def _fusion_kind(members: List[str]) -> str:
    for op in _HEAVY:
        if op in members:
            return "reduce" if op == "reduce-window" else op
    work = {op for op in members if op not in _FREE}
    if len(work) == 1 and next(iter(work)) in MOVES:
        return next(iter(work))
    return "fusion"


def parse(hlo_text: str) -> List[Instr]:
    """Every scheduled instruction of ``hlo_text`` (one with an
    ``estimated_cycles``), in program order."""
    scopes = traceparse.scope_index(hlo_text)[0]
    members: Dict[str, List[str]] = {}      # computation -> member opcodes
    layouts: Dict[str, str] = {}            # instruction -> result layout
    rows = []
    current = None
    for line in hlo_text.splitlines():
        im = _INSTR_RE.match(line)
        if im is None:
            cm = traceparse._COMP_RE.match(line)
            if cm:
                current = cm.group(1)
            continue
        name, result, opcode = im.groups()
        shape, layout, nbytes = _first_array(result)
        layouts[name] = layout
        members.setdefault(current, []).append(opcode)
        cm = _CYCLES_RE.search(line)
        if cm is None:
            continue
        operands = traceparse._OPERAND_RE.findall(line[im.end():].split("), ")[0])
        calls = traceparse._CALLS_RE.search(line) if opcode == "fusion" else None
        rows.append((name, current, opcode, calls and calls.group(1), shape,
                     layout, nbytes, int(cm.group(1)), operands))
    out = []
    for (name, comp, opcode, calls, shape, layout, nbytes, cycles,
         operands) in rows:
        kind = _fusion_kind(members.get(calls, [])) if calls else opcode
        reads = next((layouts[o] for o in operands if layouts.get(o)), "")
        out.append(Instr(name, comp, opcode, kind, shape, layout, nbytes,
                         cycles, reads, scopes.get(name, "")))
    return out


def report(instrs: List[Instr], min_bytes: int = 1 << 20,
           computation: Optional[str] = None) -> dict:
    """``{"total_cycles", "by_kind": {kind: [count, cycles]}, "moves":
    {scope: [row, ...]}}`` over the instructions whose computation's name
    matches ``computation`` (a regular expression; all if None)."""
    if computation is not None:
        pat = re.compile(computation)
        instrs = [i for i in instrs if pat.search(i.computation)]
    by_kind: Dict[str, List[int]] = {}
    moves: Dict[str, list] = {}
    for i in instrs:
        cell = by_kind.setdefault(i.kind, [0, 0])
        cell[0] += 1
        cell[1] += i.cycles
        if i.kind in MOVES and i.nbytes >= min_bytes:
            moves.setdefault(i.scope or "(no scope)", []).append(
                {"name": i.name, "kind": i.kind, "shape": i.shape,
                 "bytes": i.nbytes, "reads": i.reads, "writes": i.layout,
                 "cycles": i.cycles})
    return {"total_cycles": sum(i.cycles for i in instrs),
            "instructions": len(instrs),
            "by_kind": dict(sorted(by_kind.items(), key=lambda kv: -kv[1][1])),
            "moves": dict(sorted(moves.items()))}


def render(rep: dict) -> str:
    total = rep["total_cycles"] or 1
    lines = [f"{rep['instructions']} scheduled instructions, "
             f"{rep['total_cycles']:,} estimated cycles "
             "(XLA's cost model, not a time)"]
    for scope, rows in rep["moves"].items():
        cycles = sum(r["cycles"] for r in rows)
        lines.append(f"{scope}: {len(rows)} moves, {cycles:,} cycles")
        for r in rows:
            lines.append(
                f"  {r['kind']:9s} {r['name']:28s} {r['shape']:24s} "
                f"{r['bytes'] / 1e6:8.1f} MB  {r['reads'] or '?':>12s} -> "
                f"{r['writes']:12s} {r['cycles']:>10,}")
    lines.append("totals by opcode:")
    for kind, (n, cycles) in rep["by_kind"].items():
        lines.append(f"  {kind:14s} {n:5d} {cycles:>12,} "
                     f"{100 * cycles / total:6.2f} %")
    moved = Counter()
    for rows in rep["moves"].values():
        for r in rows:
            moved[r["kind"]] += r["cycles"]
    lines.append("of which the listed moves: " + (", ".join(
        f"{k} {v:,}" for k, v in moved.most_common()) or "none"))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("hlo", help="text of a compiled program")
    ap.add_argument("--min-mb", type=float, default=1.0,
                    help="least size of a listed move's result (default 1)")
    ap.add_argument("--computation", default=None,
                    help="only instructions of computations whose name "
                         "matches this regular expression (a loop's body)")
    ap.add_argument("--json", default=None, help="also write the report here")
    args = ap.parse_args(argv)
    with open(args.hlo) as f:
        rep = report(parse(f.read()), int(args.min_mb * 1e6), args.computation)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
    print(render(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
