"""Record the fixture of the scope readers' tests from one traced chip run:

    python3 benchmarks/tools/record_scoped.py --workload sd14.edit-replace \\
        --seed <n> --seconds 10 --out chiprun_out/fixture

runs the cell as ``run.py --trace 1`` does (the result line is printed the
same way) and writes ``<out>/trace_<config>_scoped_2steps.json.gz``, two
denoising steps cut from the middle of the first traced call
(``lib/trace.py:Trace.to_dict``), and ``<out>/trace_<config>_scoped_index.json.gz``,
the program's scope index for the instructions that ran in them
(``{module: [{instruction: scope}, {fusion: {scope: members}}]}``).
``tests/benchmark/test_benchmark_scopes.py`` reads the pair."""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def cut_steps(trace, steps: int = 2, skip: int = 10):
    """``steps`` whole steps of the sampling loop of the first device, after
    the first ``skip``: a step's bounds are the starts of a leaf instruction
    that the loop runs once a step. The loop's own event is clipped to the
    cut, and one ``bench:call`` span covers it."""
    from benchmarks.lib import trace as T

    plane, ops = next(iter(trace.devices.items()))
    loop = next(o for o in ops if o.category == "while")
    inside = [o for o in ops if o.leaf and o.loop and o.start >= loop.start
              and o.end <= loop.end]
    marker = inside[0].name
    bounds = [o.start for o in inside if o.name == marker]
    lo, hi = bounds[skip], bounds[skip + steps]
    kept = [T.Op(loop.name, lo, hi - lo, loop.category, loop.module, loop.shape)]
    kept += [o for o in inside if lo <= o.start and o.end <= hi]
    return T.Trace(devices={plane: kept},
                   modules={plane: [(loop.module, lo, hi - lo)]},
                   spans=[(T.SPAN_PREFIX + "call", lo, hi - lo)])


def restrict(indexes: dict, trace) -> dict:
    """The indexes' entries for the instructions of ``trace``."""
    names = {}
    for ops in trace.devices.values():
        for o in ops:
            names.setdefault(o.module, set()).add(o.name)
    out = {}
    for module, pair in indexes.items():
        if pair and module in names:
            index, mixed = pair
            out[module] = [{k: v for k, v in index.items() if k in names[module]},
                           {k: v for k, v in mixed.items() if k in names[module]}]
    return out


def write(path: str, data) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(data, f, separators=(",", ":"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmarks import run as run_py

    run_py.keep_cache_in_checkout()
    from benchmarks.lib import harness, scopes

    seen = {}
    load = scopes.load

    def keep(run):
        seen["run"] = run
        return load(run)

    scopes.load = keep                       # the readers ask through the module
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    result = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                              True, _T_PROCESS)
    run = seen["run"]
    from p2p_tpu.obs import launches

    cut = cut_steps(run.trace_data)
    modules = {o.module for ops in cut.devices.values() for o in ops}
    indexes = restrict({m: launches.scope_index(m) for m in modules}, cut)
    os.makedirs(args.out, exist_ok=True)
    config = run.cell["config"]
    write(os.path.join(args.out, f"trace_{config}_scoped_2steps.json.gz"), cut.to_dict())
    write(os.path.join(args.out, f"trace_{config}_scoped_index.json.gz"), indexes)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
