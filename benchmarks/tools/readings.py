"""Readings for the output check's limits, taken on the chip at a cell's own
size, all seeds in one process:

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 11 12 13 \
        [--control-seeds 11 12 13] [--fault no_edit ...] [--seconds 4]

For every seed it runs the cell as the benchmark does (a short window, the
same set-up, the same check) and prints the numbers compared: the lower
readings. For every control seed it runs it again with the program's
bfloat16 path in the program's place (``lib/controls.py``): the upper
readings. ``--fault`` does the same with a planted fault. Not part of the
benchmark's own runs.
"""

import time

_T = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.run import keep_cache_in_checkout

    keep_cache_in_checkout()
    from benchmarks.lib import controls, harness

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))

    def one(tag, seed, ctx=None):
        t0 = time.monotonic()
        if ctx is None:
            r = harness.run_cell(manifest, args.workload, seed, args.seconds, False, _T)
        else:
            with ctx():
                r = harness.run_cell(manifest, args.workload, seed, args.seconds,
                                     False, _T)
        row = {"run": tag, "seed": seed, "correct": r["correct"],
               "attempted": r["attempted"],
               "checked": {k: v["value"] for k, v in r["checked"].items()},
               "seconds": round(time.monotonic() - t0, 1)}
        print(json.dumps(row), flush=True)

    for seed in args.seeds:
        one("program", seed)
    for seed in args.control_seeds:
        one("control_bfloat16", seed, controls.bfloat16)
    for name in args.fault:
        for seed in (args.control_seeds or args.seeds)[:3]:
            one("fault_" + name, seed, controls.FAULTS[name])
    return 0


if __name__ == "__main__":
    sys.exit(main())
