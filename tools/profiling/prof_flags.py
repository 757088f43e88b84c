"""XLA compiler-option sweep for the SD14 50-step scan.

The ``--xla_tpu_*`` options go in per program through
``jax.jit(..., compiler_options=...)``.

Each variant runs in its own child process, one at a time (this parent never
imports jax, so it never holds the chip), so a hung compile costs one
TIMEOUT line, not the whole sweep:

    python tools/profiling/prof_flags.py            # sweep driver
    python tools/profiling/prof_flags.py --inner '{"...": "..."}'
"""
import json
import os
import subprocess
import sys
import time

VARIANTS = {
    "baseline": {},
    "latency_hiding": {"xla_tpu_enable_latency_hiding_scheduler": "true"},
    "vmem_128m": {"xla_tpu_scoped_vmem_limit_kib": "131072"},
    "vmem_192m": {"xla_tpu_scoped_vmem_limit_kib": "196608"},
    "latency_vmem128": {"xla_tpu_enable_latency_hiding_scheduler": "true",
                        "xla_tpu_scoped_vmem_limit_kib": "131072"},
    "latency_vmem192": {"xla_tpu_enable_latency_hiding_scheduler": "true",
                        "xla_tpu_scoped_vmem_limit_kib": "196608"},
    # Data-formatting attack (the 11% relayout share in the round-2 trace).
    # Unknown options come back as a catchable compile error and are
    # reported FAILED — they never cost real chip time.
    "sched_features": {
        "xla_tpu_enable_all_experimental_scheduler_features": "true"},
    "latency_sched_vmem192": {
        "xla_tpu_scoped_vmem_limit_kib": "196608",
        "xla_tpu_enable_latency_hiding_scheduler": "true",
        "xla_tpu_enable_all_experimental_scheduler_features": "true"},
}


def inner(opts_json: str):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _bench_common import sd14_scan_ms_per_step

    opts = json.loads(opts_json)
    ms = sd14_scan_ms_per_step(compiler_options=opts or None)
    print(f"RESULT {ms:.2f}", flush=True)


def main():
    if "--inner" in sys.argv:
        i = sys.argv.index("--inner") + 1
        inner(sys.argv[i] if i < len(sys.argv) else "{}")
        return
    results = {}
    for name, opts in VARIANTS.items():
        t0 = time.monotonic()
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--inner",
                 json.dumps(opts)],
                timeout=900, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True).stdout
        except subprocess.TimeoutExpired:
            print(f"{name:22s}: TIMEOUT", flush=True)
            continue
        line = next((l for l in out.splitlines() if l.startswith("RESULT")),
                    None)
        if line is None:
            tail = "\n    ".join(out.splitlines()[-5:])
            print(f"{name:22s}: FAILED —\n    {tail}", flush=True)
        else:
            results[name] = float(line.split()[1])
            print(f"{name:22s}: {results[name]:.2f} ms/step "
                  f"(wall {time.monotonic() - t0:.0f}s)", flush=True)
    if results:
        best = min(results, key=results.get)
        print(f"BEST {best}: {results[best]:.2f} ms/step", flush=True)


if __name__ == "__main__":
    main()
