"""Regression watch over a BENCH_r*.json trajectory.

An archive directory (``--root``) holds one ``BENCH_rNN.json`` per round,
each with the round's parsed headline JSON line (the repo itself commits
none). Without a watch a PR could halve the serve p95 budget or double the
telemetry overhead and the next round's json would just quietly record it.
This tool is the watchdog:
compare the latest round against its predecessor on the headline keys and
exit nonzero past a configurable regression threshold.

    python tools/benchwatch.py                    # latest vs predecessor
    python tools/benchwatch.py --threshold 0.05   # tighter budget
    python tools/benchwatch.py --root DIR         # a different archive

Comparability rules (an archive may mix tiny-CPU rounds with on-chip
rounds):

- The predecessor is the most recent earlier round whose headline
  ``metric`` matches the latest round's — an on-chip sd14 round is never
  diffed against a tiny-CPU round (a 94% "regression" that is really a
  preset change). No comparable predecessor — an empty archive, a
  single-round trajectory, or a metric with no earlier twin — is an
  explicit "no comparable round" note and exit 0, never a silently-green
  table of per-key ``n/a`` rows.
- A key is compared only when both rounds carry it numerically; missing
  keys report ``n/a`` and never fail the watch (early rounds predate the
  serve/obs blocks).

Wired into ``tools/quality_gate.py`` as the opt-in ``bench_trend`` check
(``--bench-trend`` or ``--only bench_trend``); rehearsal-scale coverage in
``tests/test_benchwatch.py``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional, Tuple

#: (dotted key in the parsed round json, unit label, direction). Direction
#: says which way is better: a "higher" key regresses when it drops by
#: more than the threshold, a "lower" key when it grows by more.
HEADLINE_KEYS: Tuple[Tuple[str, str, str], ...] = (
    ("value", "img/s/chip", "higher"),
    ("phase1_ms_per_step", "ms/step", "lower"),
    ("phase2_ms_per_step", "ms/step", "lower"),
    # ISSUE 15: the searched per-site reuse schedule's speedup over the
    # ungated baseline at the same operating point (the generalized-gate
    # headline; ≥1.5× is the ISSUE target, vs 1.41× for the single
    # gate). Missing in pre-schedule rounds → n/a per the contract.
    ("gate.schedule.speedup", "x", "higher"),
    # ISSUE 16: the fused in-kernel-edit attention's speedup over the
    # materialized reference at the same operating point. Only meaningful
    # on chip (CPU rehearsal runs the pallas INTERPRETER — the sub-record
    # carries `interpret: true` there); missing in pre-kernel rounds →
    # n/a per the contract.
    ("gate.kernel.speedup", "x", "higher"),
    ("serve.p95_ms", "ms", "lower"),
    ("serve.phases.two_pool_p95_ms", "ms", "lower"),
    ("serve.mesh.imgs_per_s_per_device", "img/s/device", "higher"),
    ("serve.mesh.scaling_ratio", "x", "higher"),
    ("serve.slo.premium_p99_ratio", "x", "lower"),
    ("serve.cache.amplification", "x", "higher"),
    # ISSUE 19: how long in-flight phase-2 work sat parked across an
    # elastic dp cutover (p95 over the drill's resizes, virtual-clock
    # ms — byte-stable across hosts). Missing in pre-elastic rounds →
    # n/a per the contract; direction: lower is better.
    ("serve.elastic.cutover_pause_p95_ms", "ms", "lower"),
    ("obs.overhead_pct", "%", "lower"),
    # ISSUE 18: what sampled in-engine device profiling costs the serve
    # rehearsal — capture wall time over non-capture serve wall time as
    # the profiler accounts it. Scale-dependent (CPU-rehearsal dispatches
    # are sub-ms, so trace start/stop + parse dominates); the trend, not
    # the absolute value, is the signal. Missing in pre-prodscope rounds
    # → n/a per the contract.
    ("serve.profile.overhead_pct", "%", "lower"),
    # ISSUE 14: the cost observatory's measured step MFU (flops ÷ run_s ÷
    # platform peak) — the headline the "45% MFU" verdict becomes as a
    # number. Missing in pre-cost rounds → n/a per the benchwatch
    # contract; direction: higher is better.
    ("cost.step_mfu_pct", "%", "higher"),
    ("nullinv_s_per_image", "s/image", "lower"),
)

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_rounds(root: str) -> List[Tuple[int, dict]]:
    """(round number, parsed headline dict) for every round under ``root`` that
    has one, ascending. Rounds whose measurement never produced a parsed
    line are skipped — there is nothing to
    compare."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (ValueError, OSError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and parsed.get("metric"):
            out.append((int(m.group(1)), parsed))
    out.sort(key=lambda rp: rp[0])
    return out


def lookup(parsed: dict, dotted: str) -> Optional[float]:
    """Resolve a dotted key path to a number, None when absent/non-numeric."""
    node = parsed
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)


def pick_comparison(rounds: List[Tuple[int, dict]]
                    ) -> Tuple[Optional[Tuple[int, dict]],
                               Optional[Tuple[int, dict]]]:
    """(latest, predecessor): predecessor is the most recent earlier round
    with the same headline metric (like-for-like only)."""
    if not rounds:
        return None, None
    latest = rounds[-1]
    metric = latest[1].get("metric")
    for prev in reversed(rounds[:-1]):
        if prev[1].get("metric") == metric:
            return latest, prev
    return latest, None


def compare(prev: dict, latest: dict, threshold: float) -> List[dict]:
    """One row per headline key: previous/latest values, signed delta
    fraction (positive = moved in the *better* direction), and a verdict —
    ``ok`` / ``improved`` / ``REGRESSION`` / ``n/a``."""
    rows = []
    for key, unit, direction in HEADLINE_KEYS:
        a, b = lookup(prev, key), lookup(latest, key)
        row = {"key": key, "unit": unit, "direction": direction,
               "prev": a, "latest": b}
        if a is None or b is None or a == 0:
            row["delta"] = None
            row["status"] = "n/a"
        else:
            raw = (b - a) / abs(a)
            delta = raw if direction == "higher" else -raw
            row["delta"] = delta
            row["status"] = ("REGRESSION" if delta < -threshold
                             else "improved" if delta > threshold else "ok")
        rows.append(row)
    return rows


def watch(root: str, threshold: float = 0.10) -> dict:
    """The whole check as one call (the quality gate's entry point)."""
    rounds = load_rounds(root)
    latest, prev = pick_comparison(rounds)
    if latest is None:
        return {"comparable": False, "rows": [], "regressions": [],
                "note": ("no comparable round: no BENCH_r*.json rounds "
                         "with a parsed headline in the archive")}
    if prev is None:
        return {"comparable": False, "rows": [], "regressions": [],
                "latest_round": latest[0],
                "note": (f"no comparable round: r{latest[0]:02d} "
                         f"({latest[1].get('metric')}) has no earlier "
                         f"round with the same headline metric — nothing "
                         f"like-for-like to diff")}
    rows = compare(prev[1], latest[1], threshold)
    return {"comparable": True, "latest_round": latest[0],
            "prev_round": prev[0], "threshold": threshold, "rows": rows,
            "regressions": [r for r in rows if r["status"] == "REGRESSION"]}


def render(report: dict) -> str:
    if not report["comparable"]:
        return f"bench_trend: {report['note']}"
    lines = [f"bench_trend: r{report['prev_round']:02d} -> "
             f"r{report['latest_round']:02d} "
             f"(threshold {report['threshold'] * 100:.0f}%)"]
    lines.append(f"  {'key':34s} {'prev':>12s} {'latest':>12s} "
                 f"{'delta':>8s}  verdict")
    for r in report["rows"]:
        prev = "-" if r["prev"] is None else f"{r['prev']:.4g}"
        latest = "-" if r["latest"] is None else f"{r['latest']:.4g}"
        delta = ("-" if r["delta"] is None
                 else f"{r['delta'] * 100:+.1f}%")
        lines.append(f"  {r['key']:34s} {prev:>12s} {latest:>12s} "
                     f"{delta:>8s}  {r['status']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the BENCH_r*.json rounds (default: the "
             "repo root)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression budget as a fraction (default 0.10: a "
                         "headline key moving >10%% the wrong way fails)")
    args = ap.parse_args(argv)
    report = watch(args.root, args.threshold)
    print(render(report))
    if report["regressions"]:
        keys = ", ".join(r["key"] for r in report["regressions"])
        print(f"BENCH TREND REGRESSION: {keys}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
