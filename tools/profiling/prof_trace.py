"""Capture a jax.profiler device trace of the U-Net scan and aggregate the
per-op time by category, parsing the chrome-format trace directly.

    python tools/profiling/prof_trace.py            # capture + parse
    python tools/profiling/prof_trace.py --parse D  # re-parse existing dir

Only the process that holds the chip can trace it.
"""
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))

# Coarse hlo-category buckets, matched against event names when the trace
# has no explicit category args (order matters — first match wins).
_BUCKETS = (
    ("flash-attention", re.compile(r"flash|custom-call", re.I)),
    ("convolution", re.compile(r"conv", re.I)),
    ("data formatting", re.compile(r"copy|transpose|reshape|bitcast|slice|"
                                   r"concatenate|pad|gather|scatter|"
                                   r"dynamic-update", re.I)),
    ("matmul", re.compile(r"dot|einsum", re.I)),
    ("loop fusion", re.compile(r"fusion|loop", re.I)),
    ("reduce/norm", re.compile(r"reduce|norm|softmax", re.I)),
    ("infeed/outfeed", re.compile(r"infeed|outfeed|transfer", re.I)),
)


def parse_trace_dir(logdir: str):
    """Aggregate complete ('X') events from every *.trace.json.gz under
    ``logdir`` by device lane and category bucket; print a share table."""
    paths = sorted(glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True))
    if not paths:
        print(f"no *.trace.json.gz under {logdir}", file=sys.stderr)
        return 1
    by_cat = defaultdict(float)
    lanes = defaultdict(float)
    total = 0.0
    for path in paths:
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        pid_names = {}
        tid_names = {}
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "M":
                continue
            if ev.get("name") == "process_name":
                pid_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
            elif ev.get("name") == "thread_name":
                tid_names[(ev.get("pid"), ev.get("tid"))] = (
                    ev.get("args", {}).get("name", ""))
        # Device pids carry several lanes (XLA Ops, XLA Modules, Steps…);
        # the Modules/Steps rows are ENVELOPES around the same ops — summing
        # every lane double-counts 2-3×. Keep only the per-op lane when one
        # is named; fall back to all lanes for traces without thread names.
        op_tids = {pt for pt, n in tid_names.items()
                   if re.search(r"xla ops", n, re.I)}
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            pid, tid = ev.get("pid"), ev.get("tid")
            lane = pid_names.get(pid, "")
            # Device processes only — host-side python/runtime rows would
            # count dispatch time as device time.
            if lane and not re.search(r"tpu|device|/device|xla", lane, re.I):
                continue
            if op_tids and (pid, tid) not in op_tids:
                continue
            dur = float(ev.get("dur", 0.0))  # microseconds
            name = ev.get("name", "")
            args = ev.get("args", {}) or {}
            cat = args.get("hlo_category") or next(
                (b for b, rx in _BUCKETS if rx.search(name)), "other")
            by_cat[cat] += dur
            lanes[f"{lane or '?'}/{tid_names.get((pid, tid), tid)}"] += dur
            total += dur
    if not total:
        print("no device events parsed", file=sys.stderr)
        return 1
    print(f"lanes: {dict(lanes)}")
    print(f"{'category':24s} {'ms':>10s} {'share':>7s}")
    for cat, us in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"{cat:24s} {us / 1e3:10.1f} {us / total:7.1%}")
    print(f"{'TOTAL':24s} {total / 1e3:10.1f}")
    return 0


def capture(logdir: str):
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.models import SD14, init_unet, unet_layout
    from p2p_tpu.models.unet import apply_unet
    from p2p_tpu.utils.cache import enable_persistent_cache

    from _bench_common import require_accelerator

    require_accelerator()
    enable_persistent_cache()
    cfg = SD14
    layout = unet_layout(cfg.unet)
    params = init_unet(jax.random.PRNGKey(0), cfg.unet)
    s = cfg.latent_size
    B = 4
    x = jnp.ones((B, s, s, cfg.unet.in_channels), jnp.bfloat16)
    ctx = jnp.ones((B, cfg.unet.context_len, cfg.unet.context_dim),
                   jnp.bfloat16)

    @jax.jit
    def scan(params, x, ctx):
        def body(h, t):
            eps, _ = apply_unet(params, cfg.unet, h, t, ctx, layout=layout)
            return eps, None
        out, _ = jax.lax.scan(body, x, jnp.arange(50, dtype=jnp.int32))
        return out

    np.asarray(scan(params, x, ctx))  # compile
    import shutil
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    np.asarray(scan(params, x, ctx))
    jax.profiler.stop_trace()
    print(f"trace captured under {logdir}", flush=True)


def main():
    if "--parse" in sys.argv:
        return parse_trace_dir(sys.argv[sys.argv.index("--parse") + 1])
    logdir = os.path.join(HERE, "trace_out")
    capture(logdir)
    return parse_trace_dir(logdir)


if __name__ == "__main__":
    sys.exit(main())
