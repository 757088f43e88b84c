"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics read: device busy and idle time, device operations by
class and by attention site, the programs (XLA modules) that ran, and the
idle gaps attributed to the benchmark span that was open on the host.

The trace is read once into plain lists (``load``); everything else is
arithmetic on those lists, so it runs the same on a recorded fixture
(``from_dict``) as on a fresh trace. Times are nanoseconds on the trace's own
clock, which host and device planes share.

Which operations belong to the sampling loop (``Op.loop``) is taken from
the launched program where its text exists (``program_loops``,
``mark_loops``: an instruction of a ``while``'s body or condition, or of a
computation they call), and from nesting under a ``while`` event only where
it does not, as in a recorded trace: an event the profiler lost, the
``while``'s own among them, then changes nothing of the membership. A trace
whose loop instructions do not add up to the traced calls' steps is not
read (``incomplete``).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: ``%name = <result> opcode(operands...)[, kind=kX]``: an event's name on the
#: TPU's "XLA Ops" line is the whole HLO instruction.
_HLO_RE = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<result>.*?) "
                     r"(?P<opcode>[a-z][a-z\-]*)\(")
#: Operations that only wrap others on the device's line.
CONTAINERS = ("while", "conditional", "call")
_KIND_RE = re.compile(r"kind=(k\w+)")
_SHAPE_RE = re.compile(r"^[a-z0-9]+\[([0-9,]*)\]")
#: Prefix of the benchmark's own host spans (``jax.profiler.TraceAnnotation``).
SPAN_PREFIX = "bench:"

@dataclass
class Op:
    name: str              # the HLO instruction's name, e.g. ``flash_attention.37``
    start: float
    dur: float
    category: str = ""     # opcode, with a fusion's kind: ``fusion:kOutput``
    module: str = ""       # the program it belongs to, e.g. ``jit__text2image_jit``
    shape: tuple = ()      # the result's dimensions, where it is one array
    leaf: bool = True      # no other operation runs nested inside it
    loop: bool = False     # nested inside a ``while``: the sampler's scan

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [Op]
    modules: dict = field(default_factory=dict)   # plane name -> [(name, start, dur)]
    spans: list = field(default_factory=list)     # (name, start, dur) host spans

    def to_dict(self) -> dict:
        return {
            "devices": {k: [[o.name, o.start, o.dur, o.category, o.module, list(o.shape)]
                            for o in v]
                        for k, v in self.devices.items()},
            "modules": {k: [list(m) for m in v] for k, v in self.modules.items()},
            "spans": [list(s) for s in self.spans],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        t = cls(devices={k: [Op(*o[:5], tuple(o[5])) for o in v]
                         for k, v in d["devices"].items()},
                modules={k: [tuple(m) for m in v] for k, v in d["modules"].items()},
                spans=[tuple(s) for s in d["spans"]])
        for ops in t.devices.values():
            mark_leaves(ops)
        return t


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    trace = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [parse_op(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.name.split("(")[0], e.start_ns, e.duration_ns)
                            for e in line.events]
            if ops:
                ops.sort(key=lambda o: (o.start, -o.dur))
                _assign_modules(ops, mods)
                mark_leaves(ops)
                trace.devices[plane.name] = ops
                trace.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        trace.spans.append((e.name, e.start_ns, e.duration_ns))
    trace.spans.sort(key=lambda s: s[1])
    return trace


def parse_op(text: str, start: float, dur: float) -> Op:
    """An event of the "XLA Ops" line: short name, opcode and result shape
    out of the HLO instruction that is the event's name."""
    m = _HLO_RE.match(text)
    if not m:
        return Op(text[:80], start, dur)
    category = m.group("opcode")
    if category == "fusion":
        kind = _KIND_RE.search(text)
        category += ":" + (kind.group(1) if kind else "?")
    dims = _SHAPE_RE.match(m.group("result"))
    shape = tuple(int(x) for x in dims.group(1).split(",") if x) if dims else ()
    return Op(m.group("name"), start, dur, category, "", shape)


def _assign_modules(ops, mods) -> None:
    mods = sorted(mods, key=lambda m: m[1])
    j = 0
    for o in ops:
        if o.module:
            continue
        while j < len(mods) and mods[j][1] + mods[j][2] < o.start:
            j += 1
        if j < len(mods) and mods[j][1] <= o.start:
            o.module = mods[j][0]


def mark_leaves(ops) -> None:
    """A loop, a conditional or a call is no leaf: its time is that of the
    operations nested inside it. Everything else counts in full (the async
    starts and dones that overlap a fusion are microseconds)."""
    open_loops = []
    for o in ops:                       # sorted by (start, -dur)
        o.leaf = o.category not in CONTAINERS
        open_loops = [a for a in open_loops if a.end > o.start]
        o.loop = bool(open_loops)
        if o.category == "while":
            open_loops.append(o)


# -- the loop, from the program's text ---------------------------------------

_TEXT_COMP = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{\s*$")
_TEXT_INSTR = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = .*? ([a-z][a-z\-]*)\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation"
                     r"|false_computation)=%([^\s,}]+)")
_CALLED_SET = re.compile(r"(?:branch_computations|called_computations)=\{([^}]*)\}")
_WHILE = re.compile(r"condition=%([^\s,}]+), body=%([^\s,}]+)")


@dataclass
class Loops:
    """What a program's text says of its loops: every instruction that runs
    inside one, and for each ``while`` the instructions of its body's own
    computation (those run once an iteration) and whether the ``while``
    itself runs inside another loop."""

    names: set = field(default_factory=set)
    bodies: dict = field(default_factory=dict)   # while -> [instruction]
    nested: set = field(default_factory=set)     # whiles inside a loop


def program_loops(hlo_text: str) -> Loops:
    """``Loops`` of a compiled program's text (``compiled.as_text()``)."""
    comps, calls, whiles, current = {}, {}, [], None
    for line in hlo_text.splitlines():
        m = _TEXT_INSTR.match(line)
        if m is None:
            c = _TEXT_COMP.match(line)
            if c:
                current = c.group(1)
            continue
        name, opcode = m.groups()
        comps.setdefault(current, []).append(name)
        called = calls.setdefault(current, set())
        called.update(_CALLED.findall(line))
        for group in _CALLED_SET.findall(line):
            called.update(g.strip().lstrip("%") for g in group.split(",") if g.strip())
        if opcode == "while":
            w = _WHILE.search(line)
            if w:
                whiles.append((name, w.group(1), w.group(2)))
    inside, todo = set(), [c for _, cond, body in whiles for c in (cond, body)]
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo += calls.get(c, ())
    out = Loops(names={n for c in inside for n in comps.get(c, ())})
    for name, _, body in whiles:
        out.bodies[name] = comps.get(body, [])
        if name in out.names:
            out.nested.add(name)
    return out


def mark_loops(trace: Trace, loops: dict) -> None:
    """``Op.loop`` from ``loops`` (``{module: Loops}``) for the operations of
    a module that has them; the others keep their nesting."""
    for ops in trace.devices.values():
        for o in ops:
            known = loops.get(o.module)
            if known is not None:
                o.loop = o.name in known.names


def incomplete(trace: Trace, lo: float, hi: float, loops: dict, steps: int):
    """Why the traced window's loop does not add up to ``steps`` (the traced
    calls' denoising steps), or None where it does.

    With a program's text: every instruction of a ``while``'s body that the
    window shows is seen the same number of times (once an iteration), and
    the outermost loops' iterations sum to ``steps``. Without one: the loop
    instructions by nesting are seen ``steps`` times at the most and most
    often. A profiler that lost events, the ``while``'s among them, fails
    either: the body's instructions are seen fewer times, or none is in a
    loop."""
    seen, nested_loop = {}, set()
    for ops in trace.devices.values():
        for o in ops:
            if lo <= o.start and o.end <= hi:
                key = (o.module, o.name)
                seen[key] = seen.get(key, 0) + 1
                if o.loop and loops.get(o.module) is None:
                    nested_loop.add(key)
    ndev = max(1, len(trace.devices))
    counted, by_nesting = 0, {}
    for module, name in nested_loop:
        by_nesting.setdefault(module, []).append(seen[(module, name)] // ndev)
    for module, known in sorted(loops.items(), key=lambda mk: mk[0]):
        if known is None:
            continue
        for w, body in sorted(known.bodies.items()):
            counts = {seen[(module, n)] // ndev for n in body if (module, n) in seen}
            if len(counts) > 1:
                return (f"{module}: the body of {w} was seen {min(counts)} to "
                        f"{max(counts)} times an instruction")
            if counts and w not in known.nested:
                counted += counts.pop()
    for module, counts in sorted(by_nesting.items()):
        modal = max(set(counts), key=counts.count)
        if max(counts) > steps or modal != steps:
            return (f"{module}: the loop's instructions by nesting were seen "
                    f"{modal} times most often, {max(counts)} at most, of {steps} steps")
        counted += steps
    if steps and counted != steps:
        modules = sorted({m for m, _ in seen if m})
        return (f"the traced calls ran {steps} steps and the loops of "
                f"{', '.join(modules)} in the trace {counted} iterations"
                + ("" if counted else " (no operation of them is in a loop)"))
    return None


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_of(trace: Trace, span_name: str = SPAN_PREFIX + "call"):
    """The traced window: from the start of the first benchmark call span to
    the end of the last; where the host spans are missing, the extent of the
    device operations."""
    calls = [s for s in trace.spans if s[0] == span_name]
    if calls:
        return calls[0][1], max(s[1] + s[2] for s in calls)
    starts = [o.start for ops in trace.devices.values() for o in ops]
    ends = [o.end for ops in trace.devices.values() for o in ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran on the device, mean over devices
    (a loop's own event spans its idle gaps too, so only leaves count)."""
    if not trace.devices:
        raise ValueError("the trace holds no device operation")
    per = [union_ns(((o.start, o.end) for o in ops if o.leaf), lo, hi)
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def leaf_ops(trace: Trace, lo: float, hi: float):
    """Leaf operations that lie inside the window, of all devices."""
    return [o for ops in trace.devices.values() for o in ops
            if o.leaf and o.start >= lo and o.end <= hi]


def is_flash_kernel(op: Op) -> bool:
    """The library's flash attention as the device's line names it (a Mosaic
    custom call whose instruction carries the Pallas kernel's name)."""
    return op.category == "custom-call" and op.name.startswith("flash_attention")


def op_class(op: Op) -> str:
    """A coarse class for the breakdown, from the opcode and the
    instruction's name (the TPU's trace carries no scope names)."""
    if is_flash_kernel(op):
        return "flash_attention"
    if op.category == "custom-call":
        return "custom-call"
    if "convolution" in op.name or op.category == "convolution":
        return "conv"
    if op.category.startswith("fusion"):
        return op.category
    if op.category in ("copy", "transpose", "reshape", "bitcast", "copy-done",
                       "copy-start", "slice", "pad", "broadcast", "convert"):
        return "relayout"
    return op.category or "other"


def by_class(trace: Trace, lo: float, hi: float, loop=None) -> dict:
    """Seconds of device time by ``op_class`` (mean over devices); ``loop``
    True / False keeps only operations inside / outside the sampling loop."""
    agg = {}
    for o in leaf_ops(trace, lo, hi):
        if loop is None or o.loop == loop:
            agg[op_class(o)] = agg.get(op_class(o), 0.0) + o.dur
    ndev = max(1, len(trace.devices))
    return {k: v / ndev / 1e9 for k, v in sorted(agg.items(), key=lambda kv: -kv[1])}


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10):
    """``[[name, seconds], ...]``: device time by class of operation, apart
    for the sampling loop (``loop/``) and for what the same programs and the
    others do outside it (``outside/``: text encoding, decode, staging),
    summed over the window and averaged over devices. The single heaviest
    instruction follows its class in brackets."""
    agg, heaviest = {}, {}
    for o in leaf_ops(trace, lo, hi):
        key = ("loop/" if o.loop else "outside/") + op_class(o)
        agg[key] = agg.get(key, 0.0) + o.dur
        per = heaviest.setdefault(key, {})
        per[o.name] = per.get(o.name, 0.0) + o.dur
    ndev = max(1, len(trace.devices))
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{k} [{max(heaviest[k], key=heaviest[k].get)}]", v / ndev / 1e9]
            for k, v in top]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10):
    """``[[span, seconds], ...]``: idle time of the first device, summed by
    the innermost benchmark span open on the host when the gap began."""
    if not trace.devices:
        return []
    ops = next(iter(trace.devices.values()))
    gaps, cursor = [], lo
    for s, e in sorted((o.start, o.end) for o in ops
                       if o.leaf and o.end > lo and o.start < hi):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    agg = {}
    for gs, ge in gaps:
        open_ = [s for s in trace.spans if s[1] <= gs < s[1] + s[2]]
        name = min(open_, key=lambda s: s[2])[0] if open_ else "(no span)"
        agg[name] = agg.get(name, 0.0) + (ge - gs)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
