"""One run of one cell: set-up, the measured window, the metrics, the output
check. Everything that belongs to one configuration, traffic mix, driver kind
or metric is found by its name in ``BENCHMARK.json`` and loaded from a file
of its own, so a new one is added by adding files and entries."""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from .timing import CompileClock, Spans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmarks/
ROOT = os.path.dirname(HERE)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Run:
    """What the driver fills and the metric readers read."""

    manifest: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    on_chip: bool
    clock: CompileClock
    spans: Spans = field(default_factory=Spans)
    records: list = field(default_factory=list)   # one dict per request
    retakes: list = field(default_factory=list)   # traced calls after the window
    t_setup_done: float = 0.0
    window: tuple = (0.0, 0.0)                    # host clock
    traced: tuple = None                          # host clock, inside window
    trace_data: object = None                     # lib.trace.Trace
    trace_window: tuple = None                    # the trace's own clock, ns
    device: dict = field(default_factory=dict)
    driver: object = None                         # the traffic mix's driver module
    trace_dir: str = ""
    trace_incomplete: str = None                  # why the trace is not read

    @property
    def done(self):
        return [r for r in self.records if not r.get("failed")]

    def work_of(self, records) -> dict:
        """What ``records`` cost in U-Net rows, prompts, images and steps."""
        return self.driver.work(self, records)

    def traced_records(self):
        """Requests that began and ended inside the traced window: calls of
        the window, or the one traced again after it (``retakes``)."""
        if self.traced is None:
            return []
        lo, hi = self.traced
        return [r for r in self.done + self.retakes
                if not r.get("failed") and r["t_start"] >= lo and r["t_end"] <= hi]

    # -- the profiler, driven by the driver's loop ---------------------------

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._trace_t0 = time.monotonic()

    def stop_trace(self):
        import jax

        t1 = time.monotonic()
        jax.profiler.stop_trace()
        self.traced = (self._trace_t0, t1)


def _watch_gc():
    """Times every collection from now on; ``rows`` keeps those over 50 ms."""
    from types import SimpleNamespace

    watch = SimpleNamespace(rows=[], t0=0.0)

    def callback(phase, info):
        if phase == "start":
            watch.t0 = time.monotonic()
        elif time.monotonic() - watch.t0 > 0.05:
            watch.rows.append((info["generation"], time.monotonic() - watch.t0))

    watch.callback = callback
    gc.callbacks.append(callback)
    return watch


def read_trace(run) -> None:
    """The profiler's trace of the traced calls, loaded and judged
    (``judge_trace``)."""
    from . import trace as trace_mod

    run.trace_data = trace_mod.load(trace_mod.newest_xplane(run.trace_dir))
    run.trace_window = trace_mod.window_of(run.trace_data)
    lo, hi = run.trace_window
    run.device["busy_s"] = trace_mod.busy_s(run.trace_data, lo, hi)
    run.device["window_s"] = (hi - lo) / 1e9
    judge_trace(run)


def judge_trace(run) -> None:
    """The loop of ``run.trace_data`` marked from the launched programs'
    text (``lib/launched.py``), and the trace judged whole or not:
    ``run.trace_incomplete`` says why not, or is None."""
    from . import launched
    from . import trace as trace_mod

    loops = launched.program_loops(run)
    trace_mod.mark_loops(run.trace_data, loops)
    recs = run.traced_records()
    steps = run.work_of(recs)["steps"] if recs else 0
    lo, hi = run.trace_window
    run.trace_incomplete = trace_mod.incomplete(run.trace_data, lo, hi, loops, steps)


def read_traced_calls(run, state) -> None:
    """``read_trace``, and where the trace is not whole one more call traced
    by the driver (``trace_again``) and read in its place: a profiler that
    lost events leaves the loop short, and the metrics of such a trace would
    read low, 0 or nothing. A trace still not whole is printed as such."""
    read_trace(run)
    if run.trace_incomplete and hasattr(run.driver, "trace_again"):
        print(f"trace not read ({run.trace_incomplete}): one more call traced",
              file=sys.stderr)
        run.driver.trace_again(run, state)
        read_trace(run)
    if run.trace_incomplete:
        print(f"trace incomplete: {run.trace_incomplete}; its device_trace "
              f"metrics are left out", file=sys.stderr)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def find_cell(manifest: dict, workload: str, root: str = ROOT):
    """The cell, its configuration and its traffic mix, each from its own
    file under ``root`` (the directory that holds the manifest)."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, manifest["paths"][0], "traffic",
                                     cell["name"] + ".json"))
    return cell, config, traffic


def read_metrics(run, entries) -> dict:
    """``{name: {"value", "unit"}}`` of the entries whose reader finds
    something; none of those read from the device trace where the trace is
    incomplete."""
    metrics = {}
    for m in entries:
        if run.trace_incomplete and m["source"] == "device_trace":
            continue
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def metrics_of(manifest: dict, cell: dict, group: str):
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def run_cell(manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, require_chip: bool = True,
             root: str = ROOT) -> dict:
    """Run one cell and return the result line as a dict. ``require_chip``
    is lifted only by the CPU rehearsal, which then reports no device
    metric."""
    import jax

    cell, config, traffic = find_cell(manifest, workload, root)
    info = device_info()
    on_chip = info["platform"] == "tpu"
    if require_chip and (not on_chip or info["count"] < cell["chips"]):
        raise SystemExit(
            f"cell {workload!r} needs {cell['chips']} TPU chip(s); JAX found "
            f"{info['count']} x {info['platform']}")

    from p2p_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    run = Run(manifest=manifest, cell=cell, config=config, traffic=traffic,
              seed=seed, seconds=seconds, trace=trace, t_process=t_process,
              on_chip=on_chip, clock=CompileClock(),
              trace_dir=os.path.join(ROOT, ".bench_trace", workload))
    driver = run.driver = load_module("drivers", traffic["driver"])

    t_driver = time.monotonic()
    state = driver.prepare(run)
    # Tracing the programs leaves millions of long-lived Python objects; a
    # full collection that walks them stalls the host for seconds (3 of 13
    # runs of a batched cell had one call of 3.3-5.0 s among eleven of
    # 2.56 s). They
    # are set-up's garbage, so set-up ends by taking them out of the
    # collector's sight; any pause that still falls in the window is printed.
    gc.collect()
    gc.freeze()
    pauses = _watch_gc()
    run.t_setup_done = time.monotonic()
    print("set-up seconds: " + " ".join(
        [f"to_driver:{t_driver - t_process:.1f}"]
        + [f"{n}:{e - s:.1f}" for n, s, e, _ in run.spans.rows if n in ("weights", "warm_up")]
        + [f"all:{run.t_setup_done - t_process:.1f}"]), file=sys.stderr)
    driver.window(run, state)
    gc.callbacks.remove(pauses.callback)
    print("gc pauses over 50 ms in the window:",
          " ".join(f"gen{g}:{s:.3f}s" for g, s in pauses.rows) or "none",
          file=sys.stderr)
    run.device = dict(info, memory_peak_bytes=memory_peak_bytes())

    if trace and run.traced is not None:
        if on_chip:
            read_traced_calls(run, state)
        shutil.rmtree(run.trace_dir, ignore_errors=True)

    group = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(run, metrics_of(manifest, cell, group))

    print("request wall seconds:", " ".join(
        f"{r['t_end'] - r['t_start']:.3f}" for r in run.records), file=sys.stderr)
    checked = driver.check(run, state)
    correct = bool(checked) and all(c["value"] <= c["limit"] for c in checked.values())
    for name, c in checked.items():
        print(f"check {name} = {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    result = {
        "correct": correct,
        "attempted": len(run.records),
        "failed": len(run.records) - len(run.done),
        "metrics": metrics,
        "device": run.device,
    }
    if run.trace_incomplete:
        result["trace_incomplete"] = run.trace_incomplete
    if trace and run.trace_data is not None:
        from . import trace as trace_mod

        lo, hi = run.trace_window
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(run.trace_data, lo, hi),
            "idle_gaps": trace_mod.idle_gaps(run.trace_data, lo, hi),
        }
    result["checked"] = checked
    return result
