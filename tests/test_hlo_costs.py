"""tools/hlo_costs.py on a recorded piece of a described-v5e compile: the
parent's ResNet block (PR 31's tree), whose GroupNorm reduced a
(N, H, W, groups, C/groups) view and so had the activation copied to a
W-minor layout first."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import hlo_costs  # noqa: E402

SNIPPET = os.path.join(ROOT, "tests", "data", "hlo_costs_resnet_v5e.txt")


@pytest.fixture(scope="module")
def instrs():
    with open(SNIPPET) as f:
        return hlo_costs.parse(f.read())


def test_hlo_costs_lists_the_moves_of_a_recorded_resnet_block(instrs):
    by_name = {i.name: i for i in instrs}
    # only what the compiler gave a cost is scheduled: no member of a fusion,
    # no parameter, no bitcast
    assert sorted(by_name) == sorted([
        "fusion.60", "reduce", "broadcast_multiply_fusion.3", "broadcast.55",
        "copy.5", "multiply_reduce_fusion.3", "multiply_bitcast_fusion.1",
        "pad", "fusion.24", "copy.8", "slice_bitcast_fusion.3", "copy.10"])
    # the whole activation copied to the layout whose minor dimension is W
    copy = by_name["copy.10"]
    assert (copy.kind, copy.shape, copy.reads, copy.layout, copy.cycles) == (
        "copy", "f32[4,64,64,320]", "{3,1,2,0}", "{2,1,3,0}", 96768)
    assert copy.nbytes == 4 * 64 * 64 * 320 * 4
    assert copy.scope == "unet/down0/res0"
    # the time-embedding shift at the activation's padded size
    shift = by_name["broadcast.55"]
    assert (shift.kind, shift.shape, shift.reads, shift.cycles) == (
        "broadcast", "f32[64,4,72,320]", "{1,0}", 155662)
    # a fusion counts under its heaviest member
    assert by_name["fusion.24"].kind == "convolution"
    assert by_name["multiply_reduce_fusion.3"].kind == "reduce"
    assert by_name["multiply_bitcast_fusion.1"].kind == "fusion"
    assert by_name["pad"].nbytes == 64 * 4 * 72 * 320 * 2      # bf16

    rep = hlo_costs.report(instrs)
    assert rep["total_cycles"] == sum(i.cycles for i in instrs) == 1373673
    assert rep["by_kind"]["copy"] == [3, 2400 + 16416 + 96768]
    assert rep["by_kind"]["pad"] == [1, 184506]
    assert [r["name"] for r in rep["moves"]["unet/down0/res0"]] == [
        "broadcast.55", "pad", "copy.8", "copy.10"]
    # a size limit and a computation filter
    assert hlo_costs.report(instrs, min_bytes=22_000_000)["moves"][
        "unet/down0/res0"][0]["name"] == "broadcast.55"
    assert hlo_costs.report(instrs, computation="^nothing$")[
        "instructions"] == 0
    text = hlo_costs.render(rep)
    assert "not a time" in text.splitlines()[0]
    assert "{3,1,2,0} -> {2,1,3,0}" in text


def test_hlo_costs_cli_prints_the_report(capsys, tmp_path):
    out = tmp_path / "rep.json"
    assert hlo_costs.main([SNIPPET, "--min-mb", "8", "--json", str(out)]) == 0
    printed = capsys.readouterr().out
    assert "totals by opcode:" in printed and "copy.10" in printed
    import json

    assert json.loads(out.read_text())["total_cycles"] == 1373673
