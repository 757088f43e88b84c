"""The cell ``pixart_sigma.edit-replace`` (PixArt-Sigma at 1024²): its
files against the program's preset, and a CPU rehearsal of the cell at the
toy preset ``tiny_dit`` through ``harness.run_cell``, from a manifest, a
configuration and a traffic mix written under ``tmp_path`` from the cell's
own files, checked against the plain reference
(``benchmarks/reference/pixart_sigma.py``)."""

import copy
import json
import os
import time

import pytest

from benchmarks.lib import controls, flops, harness, pipeline

MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELL = "pixart_sigma.edit-replace"
CONFIG = harness.load_json(os.path.join(harness.HERE, "configs", "pixart_sigma.json"))
MIX = harness.load_json(os.path.join(harness.HERE, "traffic", CELL + ".json"))
TOY = "tiny_dit.edit-replace"


def test_the_file_is_the_preset_and_counts_as_pr_41_pinned():
    from p2p_tpu.models.config import PRESET_CONFIGS

    assert pipeline.program_config(CONFIG) is PRESET_CONFIGS["pixart_sigma"]
    assert CONFIG["reduced"] == [] and CONFIG["reference"] == "pixart_sigma"
    assert flops.transformer_forward_flops(CONFIG["transformer"]) == 6_633_579_773_952
    assert flops.text_encoder_flops(CONFIG["text_encoder"]) == 2_813_696_409_600
    shapes = pipeline.weight_shapes(PRESET_CONFIGS["pixart_sigma"])
    gib = 2.0 ** 30
    # DiT 1.22 GB, T5 9.79 GB, SDXL's autoencoder 0.17 GB, kernels bf16
    from benchmarks.lib import weights

    assert 10.3 < weights.tree_bytes(shapes) / gib < 10.5
    assert len(shapes["text"]["layers"]) == 24          # a list, not stacked


def test_the_cell_and_its_entries_are_in_the_manifest():
    cell = next(c for c in MANIFEST["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pixart_sigma", "edit-replace", 1)
    reading = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())}
    assert {m["name"] for m in MANIFEST["per_layer"]} - reading == {
        "model.resblock_ms_per_step", "model.self_attn_kernel_ms_per_step"}
    edit = MIX["edit"]
    assert (MIX["driver"], edit["self_max_pixels"], edit["guidance_scale"],
            edit["num_steps"], edit["kinds"], MIX["trace_calls"]) == (
        "closed_edit", 4096, 4.5, 50, ["replace"], 1)


def _toy_cell(root):
    """The cell's files at the toy preset under ``root``: the manifest with
    the cell and every per-layer entry that reads it, the configuration
    with the toy's sizes and the cell's assumptions, the mix at 4 steps."""
    from p2p_tpu.models.config import TINY_DIT

    bench = os.path.join(root, "bench")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "traffic"))
    config = dict(copy.deepcopy(CONFIG), name="tiny_dit", preset="tiny_dit",
                  **pipeline._sizes_of_program(TINY_DIT))
    mix = copy.deepcopy(MIX)
    mix["edit"].update(num_steps=4, self_max_pixels=64)
    per_layer = [dict(m, workloads=[TOY]) for m in MANIFEST["per_layer"]
                 if CELL in m.get("workloads", ())]
    manifest = dict(
        MANIFEST, paths=["bench"], run_seconds=1,
        configs=[{"name": "tiny_dit", "source": "tests only",
                  "file": "bench/configs/tiny_dit.json", "reduced": [],
                  "why": "rehearsal"}],
        workloads=[{"name": TOY, "config": "tiny_dit", "traffic": "edit-replace",
                    "chips": 1, "why": "rehearsal"}],
        per_layer=per_layer)
    for path, doc in ((os.path.join(root, "BENCHMARK.json"), manifest),
                      (os.path.join(bench, "configs", "tiny_dit.json"), config),
                      (os.path.join(bench, "traffic", TOY + ".json"), mix)):
        with open(path, "w") as f:
            json.dump(doc, f)
    return manifest


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("rehearsal_dit"))
    return root, _toy_cell(root)


def _run(toy, trace=False):
    root, manifest = toy
    return harness.run_cell(manifest, TOY, 2 ** 31 + 4207, 0.2, trace,
                            time.monotonic(), require_chip=False, root=root)


def test_the_rehearsal_is_correct_and_traced_reads_raise_nothing(toy):
    r = _run(toy)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["platform"] == "cpu"
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert r["checked"]["image_rel_err"]["value"] < 1e-3
    traced = _run(toy, trace=True)                 # every reader loads, none raises
    assert traced["correct"] is True


def test_the_control_is_not_correct(toy):
    with controls.bfloat16():
        r = _run(toy)
    assert r["correct"] is False and r["failed"] == 0
    value, limit = (r["checked"]["image_rel_err"][k] for k in ("value", "limit"))
    assert value > 3 * limit
