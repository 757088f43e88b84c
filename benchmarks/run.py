"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output. Needs the TPU
chips the cell asks for: without them it exits non-zero and prints nothing.
"""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def keep_cache_in_checkout() -> None:
    """JAX's persistent compilation cache at the fixed ``<checkout>/.jax_cache``
    and without a size cap, set before JAX is imported (the program's
    ``utils/cache.py`` then takes this directory as given). A machine's own
    ``JAX_COMPILATION_CACHE_DIR`` is not used: it would be shared by the two
    sides of a comparison, and the one on the chip tool's machines comes with
    a size cap under which one SD-1.4 program (73 MB) evicts the next, so that
    every run compiled again (PERF.md, Findings)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    keep_cache_in_checkout()
    sys.path.insert(0, ROOT)
    from benchmarks.lib import harness

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    result = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                              bool(args.trace), _T_PROCESS)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
