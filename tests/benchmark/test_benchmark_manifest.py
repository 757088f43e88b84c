"""``BENCHMARK.json`` and the files it names: every metric has a reader,
every cell its configuration and traffic file, every configuration file is
the program's preset size by size, and the prompt corpus is one token per
word with no collision in either vocabulary."""

import os
import re

import pytest

from benchmarks.lib import harness, pipeline, traffic

MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
             + [c["name"] for c in MANIFEST["workloads"] + MANIFEST["configs"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(c["chips"] == 4 for c in MANIFEST["workloads"]) <= max(
        1, len(pairs) // 4)                    # the contract's quarter of the cells


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.load_module("metrics", metric["name"]).read)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    for cell in MANIFEST["workloads"]:
        if "workloads" in metric and cell["name"] not in metric["workloads"]:
            continue
        reported = [m["name"] for m in harness.metrics_of(MANIFEST, cell, "end_to_end")]
        assert metric["moves"] in reported


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    _, config, mix = harness.find_cell(MANIFEST, cell["name"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    pipeline.program_config(config)            # the file is the preset, size by size
    assert config["reduced"] == [] and {"precision", "tokenizer", "weights"} <= set(config["assumed"])
    assert mix["why"] and mix["users"]
    assert os.path.isfile(os.path.join(harness.HERE, "drivers", mix["driver"] + ".py"))
    assert os.path.isfile(os.path.join(harness.HERE, "reference", config["reference"] + ".py"))
    assert set(mix["check"]["limits"]) and all(v > 0 for v in mix["check"]["limits"].values())


def test_a_changed_size_is_refused():
    config = harness.load_json(os.path.join(harness.HERE, "configs", "sd14.json"))
    config["unet"]["block_out_channels"][0] = 256
    with pytest.raises(ValueError, match="unet"):
        pipeline.program_config(config)


@pytest.mark.parametrize("vocab", (49408, 30522))
def test_corpus_is_one_token_per_word_without_collisions(vocab):
    from benchmarks.reference import latent_diffusion as ref

    words = sorted(set(traffic.ARTICLES + traffic.ADJECTIVES + traffic.ANIMALS
                       + traffic.VERBS + traffic.OBJECTS + traffic.PLACES
                       + traffic.EXTRAS + ("a", "in", "the")))
    cfg = {"text_encoder": {"vocab_size": vocab, "max_position_embeddings": 77}}
    ids = [int(ref.token_ids(cfg, w)[1]) for w in words]
    assert len(set(ids)) == len(words) and min(ids) >= 2


def test_same_seed_same_requests_other_seed_same_sizes():
    mix = {"edit": {"kinds": ["replace", "refine"]}, "groups": 3}
    a, b = traffic.Requests(mix, 2 ** 31 + 11), traffic.Requests(mix, 2 ** 31 + 11)
    other = traffic.Requests(mix, 7)
    for i in range(6):
        assert a(i) == b(i) and a(i) != other(i)
        assert [len(p.split()) for pair in a(i)["prompts"] for p in pair] == \
               [len(p.split()) for pair in other(i)["prompts"] for p in pair]
