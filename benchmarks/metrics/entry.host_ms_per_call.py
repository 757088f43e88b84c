"""Entry layer: wall time of a traced call that is not inside a jitted
program, in ms per call: the traced window less the union of the programs'
(XLA modules') time on the device, over the calls in it."""

from benchmarks.lib import trace as T


def read(run):
    tr = run.trace_data
    if tr is None:
        return None
    lo, hi = run.trace_window
    calls = [s for s in tr.spans if s[0] == T.SPAN_PREFIX + "call"]
    if not calls:
        return None
    mods = next(iter(tr.modules.values()), [])
    inside = (T.union_ns(((s, s + d) for _, s, d in mods), lo, hi) if mods
              else T.busy_s(tr, lo, hi) * 1e9)
    return ((hi - lo) - inside) / len(calls) / 1e6
