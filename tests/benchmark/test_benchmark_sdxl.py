"""What PR 36 adds to the benchmark, all in new files and appended entries:
the configuration ``sdxl`` (SDXL-base-1.0 at 1024 x 1024) size by size
against the program's preset, its cell ``sdxl.edit-replace``, the plain
reference of this member of the family (``reference/latent_diffusion_xl.py``),
the cell's CPU rehearsal at the toy preset ``tiny_xl`` (``rehearsal_xl/``) with
``correct`` true, and false under the control and each planted fault, and the
two readers ``device.weights_gib`` and ``model.cond_ms_per_call``. Everything
in the manifest is found by name: nothing here pins an order, a count or a
last element of its lists."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.lib import controls, flops, harness, launched, pipeline
from benchmarks.lib import trace as T

from test_benchmark_flops import THREE_LEVEL  # noqa: E402
from test_benchmark_scopes import fake_run, named  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL_XL = os.path.join(HERE, "rehearsal_xl")
CELL = "sdxl.edit-replace"
CELL_XL = "tiny_xl.edit-replace"
MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
SDXL = harness.load_json(os.path.join(harness.HERE, "configs", "sdxl.json"))
MIX = harness.load_json(os.path.join(harness.HERE, "traffic", "sdxl.edit-replace.json"))
NEW_READERS = ("device.weights_gib", "model.cond_ms_per_call")
MODULE = "jit__text2image_jit"
ENCODE = "jit__encode_jit"


# -- the configuration and the cell --------------------------------------------

def test_sdxl_json_is_the_programs_preset_at_published_widths():
    from p2p_tpu.models.config import PRESET_CONFIGS

    pc = pipeline.program_config(SDXL)             # size by size, unedited
    assert pc is PRESET_CONFIGS["sdxl"]
    assert SDXL["unet"] == THREE_LEVEL             # the member PR 33 counted by hand
    assert SDXL["reduced"] == [] and SDXL["reference"] == "latent_diffusion_xl"
    assert set(SDXL["source_files"]) == {
        "unet/config.json", "text_encoder/config.json", "text_encoder_2/config.json",
        "vae/config.json", "scheduler/scheduler_config.json", "model_index.json"}
    first, second = SDXL["text_encoder"]
    assert (first["hidden_size"], first["num_hidden_layers"], first["hidden_act"]) == \
        (768, 12, "quick_gelu") and "projection_dim" not in first
    assert (second["hidden_size"], second["num_hidden_layers"], second["num_attention_heads"],
            second["hidden_act"], second["projection_dim"]) == (1280, 32, 20, "gelu", 1280)
    assert first["hidden_size"] + second["hidden_size"] == SDXL["unet"]["cross_attention_dim"]
    assert (SDXL["image_size"], SDXL["guidance_scale"], SDXL["vae"]["scaling_factor"]) == \
        (1024, 5.0, 0.13025)
    # what ``pipeline.py`` compares no key of, pinned against the preset here
    assert tuple(SDXL["size_conditioning"]) == pc.unet.addition_sizes == \
        (1024, 1024, 0, 0, 1024, 1024)
    assert SDXL["addition_time_embed_dim"] == pc.unet.addition_time_dim == 256
    assert second["projection_dim"] + 6 * 256 == SDXL["unet"]["addition_embed_in"]
    assert SDXL["precision"]["kernels"] == "bfloat16" == pc.unet.kernel_dtype
    assert {t.kernel_dtype for t in pc.towers} | {pc.vae.kernel_dtype} == {"bfloat16"}
    assert [(t.output_layer, t.final_norm) for t in pc.towers] == [(-2, False)] * 2
    assert {"precision", "tokenizer", "weights", "scheduler", "size_conditioning",
            "unconditional", "text_towers", "transformer_depth"} <= set(SDXL["assumed"])


def test_sdxl_and_its_cell_are_in_the_manifest():
    entry = named(MANIFEST["configs"], "sdxl")
    assert (entry["file"], entry["reduced"]) == ("benchmarks/configs/sdxl.json", [])
    assert entry["source"] == SDXL["source"] == \
        "https://huggingface.co/stabilityai/stable-diffusion-xl-base-1.0"
    cell = named(MANIFEST["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdxl", "edit-replace", 1)
    # every per-layer metric reads the cell through scopes and the launch
    for metric in MANIFEST["per_layer"]:
        assert CELL in metric["workloads"], metric["name"]
    for name in NEW_READERS:
        metric = named(MANIFEST["per_layer"], name)
        assert {"sd14.edit-replace", "sd21.edit-replace", CELL} <= set(metric["workloads"])
    weights, cond = (named(MANIFEST["per_layer"], n) for n in NEW_READERS)
    assert (weights["unit"], weights["source"], weights["layer"], weights["moves"]) == \
        ("GiB", "program_counter", "Device", "peak_hbm_gib")
    assert (cond["unit"], cond["source"], cond["layer"], cond["moves"]) == \
        ("ms", "device_trace", "Model", "images_per_s")
    edit = MIX["edit"]
    assert (MIX["driver"], edit["self_max_pixels"], edit["store"], edit["num_steps"],
            edit["guidance_scale"], edit["kinds"], MIX["trace_calls"],
            MIX["check"]["requests"]) == ("closed_edit", 1024, True, 50, 5.0,
                                          ["replace"], 1, 1)


def test_parameters_and_memory_of_the_weights():
    """2.567 B + 123 M + 695 M + 83.7 M parameters; with kernels in bfloat16
    (and the two token tables, 101 M entries, in float32 with the biases and
    norms) 7.14 GB, 45 % of a v5e's 16 GB before any activation, where
    float32 would be 13.9 GB."""
    import jax

    shapes = pipeline.weight_shapes(pipeline.program_config(SDXL))
    assert isinstance(shapes["text"], list) and len(shapes["text"]) == 2
    count = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count == {"unet": 2_567_463_684, "text": 123_060_480 + 694_659_840,
                     "vae": 83_653_863}
    stored = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert 7.10e9 < stored < 7.18e9 and 0.44 < stored / 16e9 < 0.45
    assert 4 * sum(count.values()) > 13.8e9


def test_work_of_a_call():
    """6.725 TFLOP a row of the U-Net's batch, three quarters of it in
    transformer blocks; a call of 200 rows, 4 prompts and 2 decodes."""
    uc = SDXL["unet"]
    row = flops.unet_forward_flops(uc)
    assert row == 6_724_783_370_240
    sites = flops.unet_sites(uc)
    blocks = sum(flops.transformer_flops(uc, p, c, depth=1) - 2 * flops._conv(p, c, c, 1)
                 for _, _, p, c in sites)
    assert 0.74 < blocks / row < 0.78
    assert [p for _, _, p, _ in sites].count(4096) == 10 and len(sites) == 70
    assert 1.36e15 < flops.work_flops(SDXL, 200, 0, 4, 2) < 1.37e15


# -- the reference -------------------------------------------------------------

def test_reference_imports_nothing_of_the_program_or_the_other_references():
    path = os.path.join(harness.HERE, "reference", "latent_diffusion_xl.py")
    import ast

    with open(path) as f:
        nodes = list(ast.walk(ast.parse(f.read())))
    imports = {a.name for n in nodes if isinstance(n, ast.Import) for a in n.names} \
        | {n.module for n in nodes if isinstance(n, ast.ImportFrom)}
    assert imports == {"__future__", "hashlib", "jax", "jax.numpy", "math", "numpy"}


def test_reference_widens_each_kernel_where_it_is_used():
    """A bfloat16 kernel goes into the product as float32, and the tree that
    is handed in is not copied: the jitted function's only float32 forms of a
    kernel are inside it."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", "latent_diffusion_xl")
    k = jax.random.normal(jax.random.PRNGKey(0), (8, 4)).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    np.testing.assert_array_equal(ref.linear({"kernel": k}, x),
                                  x @ k.astype(jnp.float32))
    assert ref.wide(k).dtype == jnp.float32
    with pytest.raises(ValueError, match="every step in full"):
        ref.make_edit_fn(SDXL, dict(MIX["edit"], kind="replace", gate=0.5))


# -- the cell's rehearsal on the CPU, at the toy preset ------------------------

def run(seed=2147483659, trace=False, manifest=None):
    manifest = manifest or harness.load_json(os.path.join(REHEARSAL_XL, "BENCHMARK.json"))
    return harness.run_cell(manifest, CELL_XL, seed, 0.2, trace, time.monotonic(),
                            require_chip=False, root=REHEARSAL_XL)


def test_rehearsal_files_state_the_toy_preset():
    from p2p_tpu.models.config import PRESET_CONFIGS

    config = harness.load_json(os.path.join(REHEARSAL_XL, "bench", "configs", "tiny_xl.json"))
    pc = pipeline.program_config(config)
    assert pc is PRESET_CONFIGS["tiny_xl"] and config["reference"] == SDXL["reference"]
    assert config["unet"]["transformer_depth"] == [0, 1, 2]
    assert config["unet"]["attention_levels"] == [False, True, True]
    assert len(config["text_encoder"]) == 2
    assert tuple(config["size_conditioning"]) == pc.unet.addition_sizes
    assert config["addition_time_embed_dim"] == pc.unet.addition_time_dim


@pytest.mark.parametrize("seed", (2147483659, 7))
def test_rehearsal_is_correct(seed):
    r = run(seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["platform"] == "cpu"
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert r["checked"]["image_rel_err"]["value"] < 1e-3


@pytest.mark.parametrize("fault", ["bfloat16", "no_edit", "altered_answer"])
def test_control_and_planted_faults_are_not_correct(fault):
    ctx = {"bfloat16": controls.bfloat16, **controls.FAULTS}[fault]
    with ctx():
        r = run()
    assert r["correct"] is False and r["failed"] == 0
    assert any(c["value"] > 3 * c["limit"] for c in r["checked"].values())


def test_traced_rehearsal_loads_every_reader_and_none_raises(monkeypatch):
    """The rehearsal's manifest lists every per-layer metric of the
    benchmark's for the toy cell: a traced run loads each reader, none
    raises, and off the chip neither new one prints a number."""
    manifest = harness.load_json(os.path.join(REHEARSAL_XL, "BENCHMARK.json"))
    assert {m["name"] for m in manifest["per_layer"]} == \
        {m["name"] for m in MANIFEST["per_layer"]}
    for metric in manifest["per_layer"]:
        assert CELL_XL in metric["workloads"]
    loaded, load = [], harness.load_module
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name: loaded.append((kind, name)) or load(kind, name))
    r = run(trace=True, manifest=manifest)
    assert r["correct"] is True
    assert {("metrics", m["name"]) for m in manifest["per_layer"]} <= set(loaded)
    assert not set(r["metrics"]) & set(NEW_READERS)


# -- the two readers -----------------------------------------------------------

def _trace(rows, modules):
    return T.Trace.from_dict({"devices": {"/device:TPU:0": rows},
                              "modules": {"/device:TPU:0": modules}, "spans": []})


ROWS = [["fusion.9", 0, 300, "fusion:kOutput", ENCODE, []],        # a tower's product
        ["fusion.10", 300, 100, "fusion:kLoop", ENCODE, []],       # the pooling
        ["fusion.1", 1000, 50, "fusion:kOutput", MODULE, []],      # add_embed, ahead
        ["while.1", 1100, 9000, "while", MODULE, []],
        ["fusion.2", 1200, 4000, "fusion:kOutput", MODULE, []],    # in the loop
        ["fusion.3", 11000, 700, "fusion:kOutput", MODULE, []]]    # the decode
MODULES = [[ENCODE, 0, 400], [MODULE, 1000, 10700]]
TRACE = _trace(ROWS, MODULES)
INDEXES = {
    ENCODE: ({"fusion.9": "text_encoder/tower1", "fusion.10": "text_encoder/pool"}, {}),
    MODULE: ({"fusion.1": "unet/add_embed", "fusion.2": "unet/down1/attn0/ff",
              "fusion.3": "vae.decode/up3"}, {}),
}


def test_cond_ms_per_call_reads_the_towers_and_the_added_embedding(capsys):
    read = harness.load_module("metrics", "model.cond_ms_per_call").read
    run_ = fake_run(TRACE, INDEXES, steps=1)
    assert read(run_) == pytest.approx((300 + 100 + 50) / 1e6)
    # one tower and no added embedding, as sd14 and sd21: the tower alone
    one = {ENCODE: ({"fusion.9": "text_encoder", "fusion.10": "text_encoder"}, {}),
           MODULE: ({"fusion.2": "unet/down1/attn0/ff", "fusion.1": "unet/time_embed",
                     "fusion.3": "vae.decode/up3"}, {})}
    assert read(fake_run(TRACE, one, steps=1)) == pytest.approx(400 / 1e6)
    # a scope of that name inside the loop is a step's, not the call's
    inside = {ENCODE: INDEXES[ENCODE],
              MODULE: (dict(INDEXES[MODULE][0], **{"fusion.2": "unet/add_embed"}), {})}
    assert read(fake_run(TRACE, inside, steps=1)) == pytest.approx(450 / 1e6)
    # nothing to read: no index, or no scope of either name
    assert read(fake_run(TRACE, {}, steps=1)) is None
    bare = {MODULE: ({"fusion.1": "unet/time_embed", "fusion.2": "unet/down1/attn0/ff",
                      "fusion.3": "vae.decode/up3"}, {}),
            ENCODE: ({"fusion.9": "sampler/cfg", "fusion.10": "sampler/cfg"}, {})}
    assert read(fake_run(TRACE, bare, steps=1)) is None
    capsys.readouterr()


def test_weights_gib_sums_each_part_once(monkeypatch):
    from p2p_tpu.obs import launches

    read = harness.load_module("metrics", "device.weights_gib").read
    kept = {MODULE: SimpleNamespace(weights_bytes={
                "unet": {"bfloat16": 5 << 30, "float32": 1 << 20}, "vae": {"bfloat16": 1 << 28}}),
            ENCODE: SimpleNamespace(weights_bytes={"text": {"bfloat16": 3 << 29}}),
            "jit_other": SimpleNamespace(weights_bytes={"unet": {"bfloat16": 1 << 30}})}
    monkeypatch.setattr(launches, "programs",
                        lambda module=None: [kept[module]] if module in kept else [])
    on = SimpleNamespace(on_chip=True, trace_data=TRACE, trace_window=(0, 12000))
    assert read(on) == pytest.approx(5 + 2 ** -10 + 0.25 + 1.5)
    # a part two programs take is the larger of the two, not their sum
    both = _trace(ROWS + [["copy.7", 11800, 10, "copy", "jit_other", []]],
                  MODULES + [["jit_other", 11800, 10]])
    assert read(SimpleNamespace(on_chip=True, trace_data=both,
                                trace_window=(0, 12000))) == pytest.approx(6.75 + 2 ** -10)
    assert read(SimpleNamespace(on_chip=False, trace_data=TRACE,
                                trace_window=(0, 12000))) is None
    assert read(SimpleNamespace(on_chip=True, trace_data=None)) is None
    # a program that keeps no such record (any tree before this PR)
    monkeypatch.setattr(launches, "programs",
                        lambda module=None: [SimpleNamespace(self_sites={})])
    assert read(on) is None
    monkeypatch.setattr(launches, "programs", lambda module=None: [])
    assert read(on) is None


def test_weights_of_a_program_this_process_launched():
    """After a rehearsal run the registry holds the toy cell's launches, and
    what they say about their weights is the tree the harness built."""
    from p2p_tpu.obs import launches

    assert run(trace=True)["correct"] is True
    sampling, encode = launched.newest(MODULE), launched.newest(ENCODE)
    assert set(sampling.weights_bytes) == {"unet", "vae"}
    assert set(encode.weights_bytes) == {"text"}
    assert set(sampling.weights_bytes["unet"]) == {"bfloat16", "float32"}
    assert sampling.decode_chunks >= 1 and sampling.unet_depth == (0, 1, 2)
    assert f"decode_chunks {sampling.decode_chunks}; weights_bytes" in sampling.describe_model()
    assert launches.programs(MODULE)
