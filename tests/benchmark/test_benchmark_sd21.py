"""What PR 29 adds to the benchmark, all in new files: the configuration
``sd21`` (SD-2.1 at 768 x 768) size by size against the program's preset, the
operation count at its shapes against XLA's, the plain reference for
v-prediction (``reference/latent_diffusion_v.py``) and its blocked attention,
the cell's CPU rehearsal at a toy v-prediction preset (``rehearsal_v/``) with
``correct`` true, and false under the control and each planted fault, and the
two readers of self-attention time by site class (``lib/self_sites.py``) on
the recorded SD-1.4 trace and in the rehearsal. Since PR 33 the classes are
what a launch recorded (``Launch.self_sites``), handed in with the recorded
trace, and the temporaries of the launched program are read beside them
(``lib/launched.py``, ``device.program_temp_gib``)."""

import gzip
import json
import os
import time

import numpy as np
import pytest

from benchmarks.lib import (controls, controls_v, flops, harness, launched, pipeline,
                            scopes, self_sites)
from benchmarks.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL_V = os.path.join(HERE, "rehearsal_v")
CELL_V = "tiny_v.edit-replace"
MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
SD21 = harness.load_json(os.path.join(harness.HERE, "configs", "sd21.json"))
SD21_MIX = harness.load_json(os.path.join(harness.HERE, "traffic", "sd21.edit-replace.json"))
SD14 = harness.load_json(os.path.join(harness.HERE, "configs", "sd14.json"))
from test_benchmark_flops import THREE_LEVEL  # noqa: E402
from test_benchmark_scopes import fake_run, named  # noqa: E402
NEW_READERS = ("model.self_attn_kernel_ms_per_step", "model.self_attn_edited_ms_per_step")
MODULE = "jit__text2image_jit"
SITES = ("down0", "down2", "down4", "down6", "down8", "down10", "mid12", "up14",
         "up16", "up18", "up20", "up22", "up24", "up26", "up28", "up30")


def hows(kernel, sites=SITES):
    """A launch's record by site index: ``kernel`` for the named sites, the
    others the controller's."""
    return {2 * i: "kernel" if s in kernel else "edited" for i, s in enumerate(sites)}


#: What the launches of both cells said when the trace under ``data/`` was
#: recorded (PR 27: the store kept every map up to half the latent's side),
#: and what they say since PR 30 (a stored map is kept only for a reader).
LAUNCH_PR27 = hows(("down0", "down2", "up26", "up28", "up30"))
LAUNCH_PR30 = hows(("down0", "down2", "down4", "down6", "up20", "up22", "up24",
                    "up26", "up28", "up30"))


# -- the configuration -------------------------------------------------------

def test_sd21_json_is_the_programs_preset_at_published_widths():
    from p2p_tpu.models.config import PRESET_CONFIGS

    pc = pipeline.program_config(SD21)             # size by size, unedited
    assert pc is PRESET_CONFIGS["sd21"]
    # ``pipeline.py`` compares no prediction type (the scheduler block has no
    # such key): pinned here.
    assert SD21["prediction_type"] == pc.scheduler.prediction_type == "v_prediction"
    assert SD21["reduced"] == [] and len(SD21["source_files"]) == 4
    assert SD21["reference"] == "latent_diffusion_v"
    u, t = SD21["unet"], SD21["text_encoder"]
    assert (u["sample_size"], u["attention_head_size"], u["num_attention_heads"],
            u["cross_attention_dim"]) == (96, 64, None, 1024)
    assert (t["num_hidden_layers"], t["hidden_size"], t["num_attention_heads"],
            t["hidden_act"]) == (23, 1024, 16, "gelu")
    assert {"precision", "tokenizer", "weights", "use_linear_projection",
            "scheduler"} <= set(SD21["assumed"])


#: The nineteen per-layer metrics the benchmark had when the cell was added:
#: each reader applies to it as it is, so each lists it. Looked up by name:
#: nothing here pins an order, a last element, or what later PRs append.
READ_IN_THE_CELL = (
    "entry.host_ms_per_call", "entry.call_tail_s", "sampler.step_ms",
    "model.step_mfu_pct", "model.decode_ms_per_image", "kernels.self_attn_roofline",
    "compile.setup_compile_s", "compile.window_compiles", "device.idle_pct",
    "model.resblock_ms_per_step", "model.self_attn_ms_per_step",
    "model.cross_attn_ms_per_step", "model.ff_ms_per_step",
    "sampler.outside_unet_ms_per_step", "model.vae_decode_scope_ms_per_image",
    "model.scoped_pct", "entry.span_ms_per_call", "compile.setup_trace_lower_s",
    "compile.setup_uncached_programs")


def test_sd21_and_its_cell_are_in_the_manifest():
    entry = named(MANIFEST["configs"], "sd21")
    assert (entry["file"], entry["reduced"]) == ("benchmarks/configs/sd21.json", [])
    assert entry["source"] == SD21["source"] == \
        "https://huggingface.co/stabilityai/stable-diffusion-2-1"
    cell = named(MANIFEST["workloads"], "sd21.edit-replace")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sd21", "edit-replace", 1)
    for name in READ_IN_THE_CELL:
        assert "sd21.edit-replace" in named(MANIFEST["per_layer"], name)["workloads"], name
    edit = SD21_MIX["edit"]
    assert (SD21_MIX["driver"], edit["self_max_pixels"], edit["store"],
            edit["num_steps"], SD21_MIX["trace_calls"]) == ("closed_edit", 576, True, 50, 1)


def test_parameters_and_memory_of_the_weights():
    """865.9 M + 340.4 M + 83.7 M parameters: 5.16 GB of float32, 32 % of a
    v5e's 16 GB before any activation."""
    import jax

    shapes = pipeline.weight_shapes(pipeline.program_config(SD21))
    count = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count == {"unet": 865_910_724, "text": 340_387_840, "vae": 83_653_863}
    assert 0.25 < 4 * sum(count.values()) / 16e9 < 0.35


def test_flops_against_xla_count_of_a_batch4_sd21_unet_forward():
    """XLA counts 8.553 TFLOP for the lowered batch-4 U-Net forward at 96 x 96
    (``Lowered.cost_analysis()`` of ``apply_unet``, PR 29, on the CPU): this
    count leaves out its 1 % of softmax, norm and activation arithmetic."""
    mine = 4 * flops.unet_forward_flops(SD21["unet"])
    assert 0.98 < mine / 8.55324491776e12 < 1.0
    sites = flops.unet_sites(SD21["unet"])
    assert [p for _, _, p, _ in sites].count(9216) == 5 and len(sites) == 16
    # Q K^T and P V grow with the square of the pixels: 29 % of the forward's
    # operations at 96 x 96 against 15 % at SD-1.4's 64 x 64
    self_ops = sum(4 * p * p * c for _, _, p, c in sites)
    assert 0.27 < 4 * self_ops / mine < 0.31


# -- the reference -----------------------------------------------------------

def test_blocked_attention_is_the_whole_attention(monkeypatch):
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", "latent_diffusion_v")
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 3, 144, 8)) for i in range(3))
    whole = jnp.einsum("bhqk,bhkd->bhqd", ref.attention_probs(q, k), v)
    assert ref.blocked_attention(q, k, v).shape == whole.shape     # one block
    monkeypatch.setattr(ref, "PROBS_BYTES", 2 * 3 * 144 * 4 * 48)  # 48 rows
    blocked = ref.blocked_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)
    monkeypatch.setattr(ref, "PROBS_BYTES", 1)                     # a row at a time
    np.testing.assert_allclose(np.asarray(ref.blocked_attention(q, k, v)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)


def test_v_update_is_ddim_of_the_converted_epsilon():
    """x_0 = alpha x_t - sigma v and eps = alpha v + sigma x_t: the update
    from v equals the update from that eps, and ``epsilon`` is the old one."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", "latent_diffusion_v")
    old = harness.load_module("reference", "latent_diffusion")
    x, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 6, 6, 4)) for i in (1, 2))
    a_t, a_prev = jnp.float32(0.31), jnp.float32(0.47)
    eps = jnp.sqrt(a_t) * v + jnp.sqrt(1 - a_t) * x
    from_v = ref.ddim_update({"prediction_type": "v_prediction"}, x, v, a_t, a_prev)
    np.testing.assert_allclose(np.asarray(from_v),
                               np.asarray(old.ddim_update(x, eps, a_t, a_prev)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(ref.ddim_update({}, x, eps, a_t, a_prev)),
        np.asarray(old.ddim_update(x, eps, a_t, a_prev)))
    with pytest.raises(ValueError):
        ref.ddim_update({"prediction_type": "sample"}, x, v, a_t, a_prev)


# -- the cell's rehearsal on the CPU, at the toy v-prediction preset ---------

def run(seed=2147483659, trace=False, manifest=None):
    manifest = manifest or harness.load_json(os.path.join(REHEARSAL_V, "BENCHMARK.json"))
    return harness.run_cell(manifest, CELL_V, seed, 0.2, trace, time.monotonic(),
                            require_chip=False, root=REHEARSAL_V)


@pytest.mark.parametrize("seed", (2147483659, 7))
def test_rehearsal_is_correct(seed):
    r = run(seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["platform"] == "cpu"
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert r["checked"]["image_rel_err"]["value"] < 1e-3


@pytest.mark.parametrize("fault", ["bfloat16", "no_edit", "altered_answer", "epsilon_for_v"])
def test_control_and_planted_faults_are_not_correct(fault):
    ctx = {"bfloat16": controls.bfloat16, **controls.FAULTS, **controls_v.FAULTS}[fault]
    with ctx():
        r = run()
    assert r["correct"] is False and r["failed"] == 0
    assert any(c["value"] > 3 * c["limit"] for c in r["checked"].values())


def test_reference_told_epsilon_is_not_correct(monkeypatch):
    """The other side of ``epsilon_for_v``: the configuration's
    ``prediction_type`` reaches the reference."""
    find = harness.find_cell

    def as_epsilon(*a, **k):
        cell, config, traffic = find(*a, **k)
        return cell, dict(config, prediction_type="epsilon"), traffic

    monkeypatch.setattr(harness, "find_cell", as_epsilon)
    assert run()["correct"] is False


def test_rehearsal_loads_both_new_readers(monkeypatch):
    """The cell's rehearsal manifest as it is lists the two readers (PR 33):
    a traced run loads them, none raises, and off the chip none prints a
    number."""
    manifest = harness.load_json(os.path.join(REHEARSAL_V, "BENCHMARK.json"))
    for n in NEW_READERS:
        assert CELL_V in named(manifest["per_layer"], n)["workloads"]
    loaded, load = [], harness.load_module
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name: loaded.append((kind, name)) or load(kind, name))
    r = run(trace=True, manifest=manifest)
    assert r["correct"] is True
    assert {("metrics", n) for n in NEW_READERS} <= set(loaded)
    assert not set(r["metrics"]) & set(NEW_READERS)


def test_classes_and_temporaries_of_a_program_this_process_launched(monkeypatch):
    """After a rehearsal run the program's own registry holds the toy cell's
    launch: the classes are its ``self_sites`` under the layout's names, and
    XLA's temporaries of that program are a count of bytes. The metric prints
    none of it off the chip."""
    from types import SimpleNamespace

    from p2p_tpu.obs import launches

    assert run(trace=True)["correct"] is True
    config = harness.load_json(os.path.join(REHEARSAL_V, "bench", "configs", "tiny_v.json"))
    launch = launches.programs(MODULE)[-1]
    assert launched.newest(MODULE) is launch and launch.self_sites
    got = self_sites.classes(SimpleNamespace(config=config), MODULE)
    assert list(got) == flops.self_site_names(config["unet"])
    assert list(got.values()) == [s.how for _, s in sorted(launch.self_sites.items())]
    assert set(got.values()) <= {"kernel", "einsum", "edited"}
    assert launched.temp_bytes(MODULE) > 0
    assert launched.newest("jit_no_such_program") is None
    assert launched.temp_bytes("jit_no_such_program") is None
    # the reader: the largest over the modules whose loop ran in the window
    read = harness.load_module("metrics", "device.program_temp_gib").read
    trace = T.Trace.from_dict({
        "devices": {"/device:TPU:0": [["while.1", 0, 900, "while", MODULE, []],
                                      ["fusion.1", 100, 200, "fusion:kLoop", MODULE, []],
                                      ["copy.2", 950, 20, "copy", "jit_other", []]]},
        "modules": {"/device:TPU:0": [[MODULE, 0, 900]]}, "spans": []})
    on = SimpleNamespace(on_chip=True, trace_data=trace, trace_window=(0, 1000))
    assert launched.loop_modules(on) == [MODULE]
    assert read(on) == launched.temp_bytes(MODULE) / 2 ** 30
    assert read(SimpleNamespace(on_chip=False, trace_data=trace,
                                trace_window=(0, 1000))) is None
    assert read(SimpleNamespace(on_chip=True, trace_data=None)) is None
    monkeypatch.setattr(launches, "programs", lambda module=None: [])
    assert read(on) is None                        # nothing launched: nothing read


# -- self-attention time by site class ---------------------------------------

def _run_with(config, record):
    from types import SimpleNamespace

    return SimpleNamespace(config=config, self_site_hows={MODULE: record})


@pytest.mark.parametrize("config", (SD14, SD21), ids=("sd14", "sd21"))
@pytest.mark.parametrize("record,kernel", [
    (LAUNCH_PR27, ["down0", "down2", "up26", "up28", "up30"]),
    (LAUNCH_PR30, ["down0", "down2", "down4", "down6", "up20", "up22", "up24",
                   "up26", "up28", "up30"])], ids=("pr27", "pr30"))
def test_site_classes_from_a_recorded_launch(config, record, kernel):
    """The class is the launch's word for the site, the layout gives the
    name: nothing is worked out from the traffic's ``store``."""
    classes = self_sites.classes(_run_with(config, record), MODULE)
    assert list(classes) == list(SITES)
    assert [s for s, c in classes.items() if c == "kernel"] == kernel
    assert sorted(set(classes.values())) == ["edited", "kernel"]


def test_site_classes_keep_every_word_of_the_launch_and_refuse_another_layout():
    mixed = {**LAUNCH_PR30, 8: "einsum", 24: "sharded"}
    got = self_sites.classes(_run_with(SD14, mixed), MODULE)
    assert [got[s] for s in ("down0", "down8", "mid12", "up24")] == [
        "kernel", "einsum", "edited", "sharded"]
    # a launch of other sites than the configuration's layout: not this program
    fewer = {i: h for i, h in LAUNCH_PR30.items() if i < 24}
    assert self_sites.classes(_run_with(SD14, fewer), MODULE) is None
    assert self_sites.classes(_run_with(SD14, {}), MODULE) is None
    assert self_sites.classes(_run_with(SD14, LAUNCH_PR30), "jit_other") is None


def test_site_names_where_depth_differs_by_level():
    """A three-level member whose top level has no attention and whose
    transformers are 2 and 10 blocks deep: 70 self sites, a block's self
    site then its cross site, in call order."""
    names = flops.self_site_names(THREE_LEVEL)
    assert len(names) == 70 == 2 * 2 + 2 * 10 + 10 + 3 * 10 + 3 * 2
    assert names[:5] == ["down0", "down2", "down4", "down6", "down8"]
    assert names[23:26] == ["down46", "mid48", "mid50"] and names[-1] == "up138"
    record = hows(names[:4] + names[-6:], sites=names)
    classes = self_sites.classes(_run_with({"unet": THREE_LEVEL}, record), MODULE)
    assert list(classes) == names
    assert [s for s, c in classes.items() if c == "kernel"] == names[:4] + names[-6:]


def _recorded():
    def load(name):
        with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
            return json.load(f)

    trace = T.Trace.from_dict(load("trace_sd14_scoped_2steps.json.gz"))
    return trace, {m: tuple(pair) for m, pair in
                   load("trace_sd14_scoped_index.json.gz").items()}


def test_kernel_and_edited_account_for_self_attention_on_the_recorded_pair(capsys):
    """Two steps of `sd14.edit-replace` recorded on a v5e (PR 27) with the
    launch of that time handed in: the five 64 x 64 sites' ``core`` (2.08-2.09
    ms each then) and the eleven the controller had sum with the sites'
    ``qkv`` and ``out`` to the whole part."""
    trace, indexes = _recorded()
    run_ = fake_run(trace, indexes, config=SD14, self_site_hows={MODULE: LAUNCH_PR27})
    kernel, edited = (harness.load_module("metrics", n).read(run_) for n in NEW_READERS)
    whole = harness.load_module("metrics", "model.self_attn_ms_per_step").read(run_)
    err = capsys.readouterr().err                      # the scope tree, then the classes
    assert err.count("self-attention core by how the site ran") == 1
    assert kernel == pytest.approx(5 * 2.08, rel=0.01)
    assert edited == pytest.approx(5 * 0.588 + 6 * 0.012, rel=0.05)
    scoped = scopes.load(run_)
    rest = sum(r.op.dur for r in scoped.rows if r.op.loop and r.part == "self_attn"
               and r.scope.rsplit("/", 1)[-1] in ("qkv", "out")) / scoped.steps / 1e6
    assert kernel + edited + rest == pytest.approx(whole, rel=1e-6)


def test_the_same_trace_under_another_launch_moves_time_between_the_classes(capsys):
    """The reader follows the record, not the trace: told that the five
    32 x 32 sites ran on the kernel too, it counts their ``core`` there, the
    sum stays, and a class no site has is not printed as 0."""
    trace, indexes = _recorded()
    then = fake_run(trace, indexes, config=SD14, self_site_hows={MODULE: LAUNCH_PR27})
    now = fake_run(trace, indexes, config=SD14, self_site_hows={MODULE: LAUNCH_PR30})
    k0, e0, k1, e1 = (harness.load_module("metrics", n).read(r)
                      for r in (then, now) for n in NEW_READERS)
    capsys.readouterr()
    assert k1 - k0 == pytest.approx(5 * 0.588, rel=0.05)
    assert k0 + e0 == pytest.approx(k1 + e1, rel=1e-9)
    assert self_sites.core_ms_per_step(now, "einsum") is None
    assert self_sites.core_ms_per_step(now, "sharded") is None


@pytest.mark.parametrize("why", ("no_index", "no_record", "no_registry", "nothing_launched"))
def test_readers_read_nothing_without_the_launchs_record(why, monkeypatch):
    """Laid over a parent that records no launch or no sites, or with no scope
    index at all, both return None and do not raise."""
    import sys

    import p2p_tpu.obs
    from p2p_tpu.obs import launches

    trace, indexes = _recorded()
    if why == "no_index":
        run_ = fake_run(trace, {}, config=SD14, self_site_hows={MODULE: LAUNCH_PR27})
    elif why == "no_record":
        run_ = fake_run(trace, indexes, config=SD14, self_site_hows={})
    else:
        run_ = fake_run(trace, indexes, config=SD14)
        if why == "no_registry":
            monkeypatch.setitem(sys.modules, "p2p_tpu.obs.launches", None)   # ImportError
            monkeypatch.delattr(p2p_tpu.obs, "launches", raising=False)
        else:
            monkeypatch.setattr(launches, "programs", lambda module=None: [])
    assert [harness.load_module("metrics", n).read(run_) for n in NEW_READERS] == [None] * 2
