"""CPU rehearsal of the benchmark under ``benchmarks/``: every driver kind
runs end to end at the toy presets from a manifest of its own
(``rehearsal/BENCHMARK.json``, which adds cells, configurations and traffic
mixes by files and entries alone), the result line has the contract's shape,
no device metric is printed off the TPU, and ``correct`` comes out false for
the lower-precision control, for each planted fault and for a perturbed
reference."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import controls, harness

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")
CELLS = ("tiny.edit-replace", "tiny_ldm.edit-batch4", "tiny.serve-backlog")
DEVICE_METRICS = ("device.idle_pct", "model.step_mfu_pct", "kernels.self_attn_roofline",
                  "sampler.step_ms", "model.decode_ms_per_image",
                  "entry.host_ms_per_call", "peak_hbm_gib")


def run(cell, seed=2147483659, trace=False, seconds=0.2):
    manifest = harness.load_json(os.path.join(REHEARSAL, "BENCHMARK.json"))
    return harness.run_cell(manifest, cell, seed, seconds, trace,
                            time.monotonic(), require_chip=False, root=REHEARSAL)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", (False, True))
def test_result_line_shape(cell, trace):
    r = run(cell, trace=trace)
    assert list(r)[-1] == "checked" and set(r) >= {
        "correct", "attempted", "failed", "metrics", "device", "checked"}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)
    wanted = {"compile.setup_compile_s", "compile.window_compiles",
              "entry.call_tail_s"} if trace else {"images_per_s", "setup_s"}
    if cell == CELLS[2] and trace:             # the serve cell has a layer of its own
        wanted |= {"serve.overhead_pct", "serve.occupancy_pct"}
    assert set(r["metrics"]) == wanted
    assert not set(r["metrics"]) & set(DEVICE_METRICS)
    assert "breakdown" not in r and "busy_s" not in r["device"]
    for c in r["checked"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    with controls.bfloat16():
        r = run(cell)
    assert r["correct"] is False
    assert any(c["value"] > 3 * c["limit"] for c in r["checked"].values())


@pytest.mark.parametrize("cell,fault", [
    (CELLS[0], "no_edit"), (CELLS[0], "altered_answer"),
    (CELLS[1], "no_edit"), (CELLS[1], "altered_answer"),
    (CELLS[1], "swapped_groups"),
    (CELLS[2], "no_edit"), (CELLS[2], "altered_answer"), (CELLS[2], "swapped_groups")])
def test_planted_fault_is_not_correct(cell, fault):
    with controls.FAULTS[fault]():
        r = run(cell)
    assert r["correct"] is False
    # the serve engine turns a lane it cannot hand off into an error record
    assert r["failed"] == 0 or cell == CELLS[2]


def test_perturbed_reference_is_not_correct(monkeypatch):
    load = harness.load_module

    def perturbed(kind, name):
        mod = load(kind, name)
        if kind == "reference":
            noise = mod.noise
            mod.noise = lambda key, shape: noise(key, shape) * 1.02
        return mod

    monkeypatch.setattr(harness, "load_module", perturbed)
    assert run(CELLS[0])["correct"] is False


def test_failed_request_is_counted(monkeypatch):
    import p2p_tpu.engine.sampler as sampler

    calls = {"n": 0}
    orig = sampler.text2image

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("planted")
        return orig(*a, **k)

    monkeypatch.setattr(sampler, "text2image", flaky)
    r = run(CELLS[0], seconds=0.5)
    assert r["failed"] == 1 and r["attempted"] >= 3


def test_run_py_refuses_without_a_tpu():
    """The command itself has no CPU path: no result line, non-zero exit."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"),
         "--workload", "sd14.edit-replace", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
