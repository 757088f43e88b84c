"""The trace reduction of ``benchmarks/lib/trace.py``: on a hand-made trace
whose every number can be worked by hand, and on 110 ms cut from the first
traced chip run of ``sd14.edit-replace`` (PR 26: one TPU v5e, two and a bit
denoising steps of the batch-4 U-Net)."""

import gzip
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.lib import harness
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_sd14_edit_110ms.json.gz")


def hand_made() -> T.Trace:
    ops = [["while.1", 100, 800, "while", "jit_sample", []],
           ["fusion.1", 100, 200, "fusion:kOutput", "jit_sample", [4, 8]],
           ["flash_attention.2", 300, 200, "custom-call", "jit_sample",
            [2, 8, 4096, 40]],
           ["fusion.3", 600, 300, "fusion:kLoop", "jit_sample", []],
           ["copy.9", 950, 50, "copy", "jit_sample", []]]
    return T.Trace.from_dict({
        "devices": {"/device:TPU:0": ops},
        "modules": {"/device:TPU:0": [["jit_sample", 100, 900]]},
        "spans": [["bench:call", 0, 1000], ["bench:land", 480, 520]]})


def test_busy_idle_and_gap_attribution_by_hand():
    tr = hand_made()
    lo, hi = T.window_of(tr)
    assert (lo, hi) == (0, 1000)
    assert T.busy_s(tr, lo, hi) == pytest.approx(750e-9)
    assert dict(map(tuple, T.idle_gaps(tr, lo, hi))) == pytest.approx(
        {"bench:call": 100e-9, "bench:land": 150e-9})
    assert T.union_ns([(0, 10), (5, 20), (30, 40)], 2, 35) == 23


def test_loop_leaves_and_classes_by_hand():
    tr = hand_made()
    ops = {o.name: o for o in tr.devices["/device:TPU:0"]}
    assert not ops["while.1"].leaf and not ops["while.1"].loop
    assert all(ops[n].leaf and ops[n].loop
               for n in ("fusion.1", "flash_attention.2", "fusion.3"))
    assert ops["copy.9"].leaf and not ops["copy.9"].loop
    assert T.by_class(tr, 0, 1000, loop=True) == pytest.approx(
        {"fusion:kLoop": 300e-9, "fusion:kOutput": 200e-9, "flash_attention": 200e-9})
    assert T.by_class(tr, 0, 1000, loop=False) == pytest.approx({"relayout": 50e-9})
    assert dict(map(tuple, T.top_ops(tr, 0, 1000))) == pytest.approx({
        "loop/fusion:kLoop [fusion.3]": 300e-9,
        "loop/flash_attention [flash_attention.2]": 200e-9,
        "loop/fusion:kOutput [fusion.1]": 200e-9,
        "outside/relayout [copy.9]": 50e-9})
    assert T.top_ops(tr, 0, 1000, 1)[0][0] == "loop/fusion:kLoop [fusion.3]"


@pytest.mark.parametrize("text,name,category,shape", [
    ("%while.3 = (s32[]{:T(128)}, f32[2,64,64,4]{1,3,2,0:T(4,128)}, s32[50]{0:T(128)S(1)}) "
     "while((s32[]{:T(128)}, f32[2,64,64,4]) %tuple.1), condition=%c, body=%b",
     "while.3", "while", ()),
    ("%flash_attention.37 = f32[4,8,4096,40]{3,2,1,0:T(8,128)} custom-call(f32[4,8,4096,40]"
     "{3,2,1,0:T(8,128)} %bitcast.5678), custom_call_target=\"tpu_custom_call\"",
     "flash_attention.37", "custom-call", (4, 8, 4096, 40)),
    ("%fusion.328 = bf16[2,77,768]{2,1,0:T(8,128)(2,1)S(1)} fusion(f32[2,77,768]{2,1,0} "
     "%reshape.1), kind=kOutput, calls=%fused_computation.326",
     "fusion.328", "fusion:kOutput", (2, 77, 768)),
])
def test_parse_op_reads_the_hlo_instruction(text, name, category, shape):
    op = T.parse_op(text, 0.0, 1.0)
    assert (op.name, op.category, op.shape) == (name, category, shape)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return T.Trace.from_dict(json.load(f))


def test_recorded_trace_reduces_as_on_the_chip(recorded):
    lo, hi = T.window_of(recorded)
    assert (hi - lo) == pytest.approx(110e6)
    busy = T.busy_s(recorded, lo, hi)
    assert 0.90 < busy / 0.110 < 1.0                      # the chip read 0.46 % idle overall
    ops = recorded.devices["/device:TPU:0"]
    assert [o.name for o in ops if not o.leaf] == ["while.3"]
    assert sum(o.loop for o in ops) > 5000
    classes = T.by_class(recorded, lo, hi, loop=True)
    assert list(classes)[:2] == ["fusion:kOutput", "flash_attention"]
    gaps = dict(map(tuple, T.idle_gaps(recorded, lo, hi)))
    assert set(gaps) <= {"bench:call", "bench:controller", "bench:text2image", "bench:land"}
    assert sum(gaps.values()) == pytest.approx(0.110 - busy, rel=1e-6)


def test_flash_roofline_reader_on_the_recorded_trace(recorded):
    lo, hi = T.window_of(recorded)
    run = SimpleNamespace(trace_data=recorded, trace_window=(lo, hi), on_chip=True,
                          device={"kind": "TPU v5 lite"})
    share = harness.load_module("metrics", "kernels.self_attn_roofline").read(run)
    # 4 * 4 * 8 * 4096^2 * 40 = 85.9 GFLOP in 2.08 ms of a 197 TFLOP/s chip
    assert share == pytest.approx(100 * 85.9e9 / 197e12 / 2.08e-3, rel=0.02)
    run.on_chip = False
    assert harness.load_module("metrics", "kernels.self_attn_roofline").read(run) is None
    with pytest.raises(KeyError):
        harness.load_module("lib", "peaks").peaks_for("TPU v9")
