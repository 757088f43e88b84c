"""The scope readers of ``benchmarks/lib/scopes.py`` (PR 27): on two
denoising steps cut from a traced chip run of ``sd14.edit-replace`` with the
program's own scope index for the instructions that ran in them (one TPU v5e;
``benchmarks/tools/record_scoped.py`` wrote the pair), on the program's span
ring and compile ledger, against a program that offers none of them, and in
the CPU rehearsal of the benchmark, where every reader is loaded and reads
nothing (the rehearsal's own manifest lists them since PR 33). Entries of a
manifest are found by name (``per_layer_named``): nothing here depends on
where an entry stands in its list or on how many a later PR appends."""

import gzip
import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

from benchmarks.lib import harness, scopes
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MODULE = "jit__text2image_jit"
STEP_METRICS = ("model.resblock_ms_per_step", "model.self_attn_ms_per_step",
                "model.cross_attn_ms_per_step", "model.ff_ms_per_step",
                "sampler.outside_unet_ms_per_step")
SCOPE_METRICS = STEP_METRICS + ("model.vae_decode_scope_ms_per_image",
                                "model.scoped_pct")
#: PR 27's ten entries, and PR 33's three that read the program's launch
#: registry: what a program without registry, spans or ledger leaves silent.
PR27_METRICS = SCOPE_METRICS + ("entry.span_ms_per_call", "compile.setup_trace_lower_s",
                                "compile.setup_uncached_programs")
LAUNCH_METRICS = ("model.self_attn_kernel_ms_per_step",
                  "model.self_attn_edited_ms_per_step", "device.program_temp_gib")


def named(entries, name):
    """The one entry of a manifest's list that carries ``name``."""
    found = [e for e in entries if e["name"] == name]
    assert [e["name"] for e in found] == [name], f"{name}: {found}"
    return found[0]


def per_layer_named(manifest, names):
    return [named(manifest["per_layer"], n) for n in names]


def _read(name, run):
    return harness.load_module("metrics", name).read(run)


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    trace = T.Trace.from_dict(_load("trace_sd14_scoped_2steps.json.gz"))
    indexes = {m: tuple(pair) for m, pair in
               _load("trace_sd14_scoped_index.json.gz").items()}
    return trace, indexes


def fake_run(trace, indexes, steps=2, **more):
    """What the readers ask of a run: the trace, its window, the work of the
    traced records, and (in place of the program) the recorded indexes."""
    lo, hi = T.window_of(trace)
    records = [{"t_start": 10.0, "t_end": 12.5, "images": 2}]
    fields = dict(
        trace_data=trace, trace_window=(lo, hi), scope_indexes=indexes,
        traced=(10.0, 12.5), on_chip=True, device={"kind": "TPU v5 lite"},
        spans=SimpleNamespace(rows=[("call", 10.0, 12.5, 0)]),
        t_process=0.0, t_setup_done=9.0,
        traced_records=lambda: records, done=records,
        work_of=lambda recs: {"steps": steps, "images": 2, "prompts": 4,
                              "unet_rows_full": 4 * steps, "unet_rows_cached": 0})
    fields.update(more)
    return SimpleNamespace(**fields)


def test_five_parts_sum_to_the_step_on_the_recorded_pair(recorded, capsys):
    run = fake_run(*recorded)
    step = _read("sampler.step_ms", run)
    parts = {m: _read(m, run) for m in STEP_METRICS}
    assert sum(parts.values()) == pytest.approx(step, rel=0.005)
    assert 46.0 < step < 48.0                      # the chip read 46.94 ms a step
    assert _read("model.scoped_pct", run) >= 97.0
    # what the records predicted (ISSUE 27) and the chip found (PERF.md)
    assert 15.0 < parts["model.resblock_ms_per_step"] < 22.0
    assert 14.0 < parts["model.self_attn_ms_per_step"] < 19.0
    assert 1.0 < parts["model.cross_attn_ms_per_step"] < 6.0
    assert 6.0 < parts["model.ff_ms_per_step"] < 10.0
    assert parts["sampler.outside_unet_ms_per_step"] < 1.5
    # the cut holds no decode
    assert _read("model.vae_decode_scope_ms_per_image", run) is None
    err = capsys.readouterr().err
    assert "scope tree, loop:" in err and "step by part (ms/step):" in err
    assert err.count("scope index:") == 1          # reduced and printed once


def test_join_is_by_module_and_instruction(recorded):
    trace, indexes = recorded
    scoped = scopes.load(fake_run(trace, indexes))
    index, mixed = indexes[MODULE]
    flash = [r for r in scoped.rows if T.is_flash_kernel(r.op)]
    assert len(flash) == 10 and all(r.scope.endswith("/core") and "/self_attn/" in r.scope
                                    for r in flash)
    assert {r.scope for r in scoped.rows if r.scope} <= set(index.values())
    ambiguous = sum(r.op.dur for r in scoped.rows if r.ambiguous)
    total = sum(r.op.dur for r in scoped.rows)
    assert 0.0 < ambiguous / total < 0.25
    assert any(r.ambiguous for r in scoped.rows if r.op.name in mixed)
    agg = scopes.tree(scoped, loop=True)
    assert agg["unet"][0] == pytest.approx(
        sum(v[0] for k, v in agg.items() if k.count("/") == 1 and k.startswith("unet/")))


def test_an_index_of_another_program_reads_low_and_nothing_else(recorded):
    trace, indexes = recorded
    index, mixed = indexes[MODULE]
    renamed = {MODULE: ({k + ".x": v for k, v in index.items()}, mixed)}
    run = fake_run(trace, renamed)
    assert _read("model.scoped_pct", run) < 90.0
    for m in SCOPE_METRICS[:-1]:
        assert _read(m, run) is None
    # no index at all (a program that keeps none): nothing, scoped_pct too
    run = fake_run(trace, {})
    assert all(_read(m, run) is None for m in SCOPE_METRICS)


@pytest.mark.parametrize("scope,part", [
    ("unet/down0/res1", "resblock"), ("unet/conv_in", "resblock"),
    ("unet/up2/skip_concat", "resblock"), ("unet/down1/downsample", "resblock"),
    ("unet/time_embed", "resblock"),
    ("unet/up3/attn2/self_attn/up15/core", "self_attn"),
    ("unet/mid0/attn0/cross_attn/mid13/qkv", "cross_attn"),
    ("unet/down0/attn1/ff", "ff"), ("unet/down0/attn1/proj_in", "ff"),
    ("sampler/cfg", "outside_unet"), ("", "outside_unet"), ("unet", "outside_unet"),
    ("vae.decode/mid", "outside_unet")])
def test_part_of_a_scope(scope, part):
    assert scopes.part_of(scope) == part and part in scopes.PARTS


@pytest.mark.parametrize("scopes_,spans_two", [
    (["unet/conv_out", "sampler/cfg"], True),
    (["unet/down0/res1", "unet/down0/attn1/proj_in"], False),
    (["unet/down0/res1", "unet/down1/res0"], True),
    (["unet/up3/attn2/self_attn/up15/qkv", "unet/up3/attn2/self_attn/up15/core"], False),
    ([], False)])
def test_a_fusion_is_ambiguous_across_second_level_scopes(scopes_, spans_two):
    assert scopes._straddles(scopes_) is spans_two


def _span(sid, parent, name, start_ms, end_ms):
    return {"event": "span_end", "span": sid, "parent": parent, "name": name,
            "t_ns": int((10.0 + end_ms / 1e3) * 1e9), "dur_ms": end_ms - start_ms}


def test_entry_spans_self_time_by_id(recorded):
    ring = [_span(1, None, "entry.controller", 0, 2),
            _span(3, 2, "entry.prepare", 3, 4), _span(4, 2, "entry.tokenize", 4, 4.5),
            _span(5, 2, "entry.encode", 4.5, 5.5), _span(6, 2, "sampler.text2image", 5.5, 8),
            _span(2, None, "entry.text2image", 2.5, 9),
            _span(7, None, "serve.batch", 20, 30),              # not the entry layer's
            _span(8, None, "entry.controller", 2600, 2602)]     # after the traced window
    run = fake_run(*recorded, ring_events=ring)
    # roots 2 + 6.5 ms: the children's time is theirs, the root keeps the rest
    assert _read("entry.span_ms_per_call", run) == pytest.approx(8.5)
    on_trace = scopes.spans_on_trace_clock(run)
    lo, _ = run.trace_window
    name, start, dur = on_trace[0]
    assert name == "entry.controller" and dur == pytest.approx(2e6)
    assert start == pytest.approx(lo, abs=10)      # the call began with it
    # a program whose spans are on another clock (before PR 27)
    old = [dict(e, ts_ms=1.0) for e in ring]
    for e in old:
        del e["t_ns"]
    assert _read("entry.span_ms_per_call", fake_run(*recorded, ring_events=old)) is None


def test_compile_ledger_readers(recorded, monkeypatch):
    from p2p_tpu.utils import cache as cache_mod

    ledger = cache_mod.CompileLedger()
    monkeypatch.setattr(cache_mod, "compile_ledger", lambda: ledger)
    clock = [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    for at, event, secs in (
            (2.0, "/jax/core/compile/jaxpr_trace_duration", 1.0),      # 1.0-2.0
            (4.0, "/jax/core/compile/jaxpr_trace_duration", 3.0),      # 1.0-4.0, nests it
            (5.0, "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5),
            (6.0, "/jax/core/compile/backend_compile_duration", 0.3),  # never offered
            (9.5, "/jax/core/compile/backend_compile_duration", 0.2)):  # after set-up
        clock[0] = at
        ledger._on_duration(event, secs, fun_name="f")
    ledger._on_event("/jax/compilation_cache/compile_requests_use_cache")
    ledger._on_event("/jax/compilation_cache/cache_hits")
    clock[0] = 7.0
    ledger._on_duration("/jax/core/compile/backend_compile_duration", 4.0, fun_name="g")
    run = fake_run(*recorded)
    assert _read("compile.setup_trace_lower_s", run) == pytest.approx(3.5)
    assert _read("compile.setup_uncached_programs", run) == 1
    run.on_chip = False                       # the rehearsal prints neither
    assert _read("compile.setup_trace_lower_s", run) is None
    assert _read("compile.setup_uncached_programs", run) is None


def test_a_program_without_registry_spans_or_ledger_reads_nothing(recorded, monkeypatch):
    """The driver lays these readers over the parent's checkout too."""
    trace, _ = recorded
    from p2p_tpu.utils import cache as cache_mod

    import p2p_tpu.obs

    monkeypatch.setitem(sys.modules, "p2p_tpu.obs.launches", None)   # ImportError
    monkeypatch.delattr(p2p_tpu.obs, "launches", raising=False)
    monkeypatch.delattr(cache_mod, "compile_ledger")
    run = fake_run(trace, None, ring_events=[{"event": "span_end", "span": 1,
                                              "parent": None, "name": "sampler.text2image",
                                              "ts_ms": 3.0, "dur_ms": 1.0}])
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    silent = [m["name"] for m in per_layer_named(manifest, PR27_METRICS + LAUNCH_METRICS)]
    assert {m: _read(m, run) for m in silent} == dict.fromkeys(silent)
    assert _read("sampler.step_ms", run) is not None     # the old ones still read


REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal")
REHEARSAL_CELLS = ("tiny.edit-replace", "tiny_ldm.edit-batch4", "tiny.serve-backlog")


@pytest.mark.parametrize("cell", REHEARSAL_CELLS)
def test_cpu_rehearsal_loads_every_new_reader(cell, monkeypatch):
    """The rehearsal's manifest as it is lists PR 27's ten entries and PR 33's
    three for the toy cells: a traced run calls every one of their readers,
    none raises, and off the chip none prints a number."""
    manifest = harness.load_json(os.path.join(REHEARSAL, "BENCHMARK.json"))
    new = per_layer_named(manifest, PR27_METRICS + LAUNCH_METRICS)
    assert all(cell in m["workloads"] for m in new)
    loaded, load = [], harness.load_module

    def noting(kind, name):
        loaded.append((kind, name))
        return load(kind, name)

    monkeypatch.setattr(harness, "load_module", noting)
    r = harness.run_cell(manifest, cell, 2147483659, 0.2, True, time.monotonic(),
                         require_chip=False, root=REHEARSAL)
    assert r["correct"] is True and r["failed"] == 0
    assert {("metrics", m["name"]) for m in new} <= set(loaded)
    assert not set(r["metrics"]) & {m["name"] for m in new}
    assert set(r["metrics"]) <= {m["name"] for m in manifest["per_layer"]}


# -- entries are found by name, wherever they stand --------------------------

DUMMY = {"name": "dummy.metric_of_a_later_pr", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "Model", "moves": "images_per_s",
         "workloads": ["sd14.edit-replace", "sd21.edit-replace"]}


@pytest.mark.parametrize("where", ("appended", "in_front", "in_the_middle"))
def test_an_entry_of_a_later_pr_moves_no_helper(where):
    """One more per-layer entry in a copy of the manifest, at the end, at the
    front or between two others: every helper of these tests finds the same
    entries as without it, and the harness hands the cell one metric more."""
    import copy

    from test_benchmark_sd21 import READ_IN_THE_CELL

    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    grown = copy.deepcopy(manifest)
    entries = grown["per_layer"]
    {"appended": entries.append, "in_front": lambda e: entries.insert(0, e),
     "in_the_middle": lambda e: entries.insert(9, e)}[where](dict(DUMMY))
    for names in (PR27_METRICS, LAUNCH_METRICS, READ_IN_THE_CELL):
        assert per_layer_named(grown, names) == per_layer_named(manifest, names)
    assert named(grown["per_layer"], DUMMY["name"]) == DUMMY
    for cell in manifest["workloads"]:
        before = harness.metrics_of(manifest, cell, "per_layer")
        after = harness.metrics_of(grown, cell, "per_layer")
        assert [m for m in after if m != DUMMY] == before and DUMMY in after
        assert harness.metrics_of(grown, cell, "end_to_end") == \
            harness.metrics_of(manifest, cell, "end_to_end")


def test_no_test_takes_a_manifests_entries_by_position_or_count():
    """No slice, index or length of a manifest's ``per_layer``, ``end_to_end``
    or ``workloads`` in any test of the benchmark: PR 27's ``[9:]`` and
    ``len(new) == 10`` kept every later PR from listing a per-layer metric."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    group = r'\[\s*"(?:per_layer|end_to_end|workloads)"\s*\]'
    pinned = re.compile(group + r"\s*\)?\s*\[|len\([^()\n]*" + group + r"\s*\)")
    found = [(os.path.basename(path), n + 1, line.strip())
             for path in sorted(glob.glob(os.path.join(here, "test_*.py")))
             for n, line in enumerate(open(path)) if pinned.search(line)]
    assert found == []
    assert pinned.search('new = manifest["per_layer"' + "][9:]")
    assert pinned.search('assert len(MANIFEST["workloads"' + "]) == 2")
    assert pinned.search('json.load(f)["end_to_end"' + "][-1]")
