"""The benchmark's builder against members of the U-Net family the program
does not run yet (PR 34): ``lib/pipeline.py`` maps a preset whose depth
differs by level, that has several text towers and a vector embedded beside
the time step, ``lib/window.py`` builds the controller from the tokenizer,
and ``lib/weights.py`` fills each leaf in the type its initialiser gives it,
in as many calls as ``FILL_BYTES`` asks for. The stand-in member is built
here from the program's own dataclasses where they hold the value; no
configuration file states it and no cell runs it."""

import copy
import dataclasses
import hashlib
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import flops, harness, pipeline, weights, window
from p2p_tpu.models import config as presets

from test_benchmark_flops import THREE_LEVEL  # noqa: E402

SD14 = harness.load_json(os.path.join(harness.HERE, "configs", "sd14.json"))
SEED = 2 ** 31 + 11


class With:
    """A configuration of the program's with attributes its dataclass does
    not have yet."""

    def __init__(self, base, **more):
        self.__dict__.update(more, _base=base)

    def __getattr__(self, name):
        return getattr(self._base, name)


# -- the stand-in: the program's side, and the file's ------------------------

STANDIN_UNET = With(
    dataclasses.replace(presets.SD21_UNET, sample_size=128,
                        block_channels=(320, 640, 1280),
                        attn_levels=(False, True, True),
                        transformer_depth=(0, 2, 10), context_dim=2048),
    addition_embed_in=2816)
STANDIN_TOWERS = (
    presets.SD14_TEXT,
    With(dataclasses.replace(presets.SD21_TEXT, hidden_dim=1280, num_layers=32,
                             num_heads=20), projection_dim=1280))
STANDIN = presets.PipelineConfig(
    "three-level", STANDIN_UNET, STANDIN_TOWERS,
    dataclasses.replace(presets.SD14_VAE, scaling_factor=0.13025), image_size=1024)

#: The file such a member would bring, written by hand in ``flops.py``'s
#: schema: the ``unet`` block is the one ``test_benchmark_flops.py`` counts.
STANDIN_FILE = {
    "name": "three_level", "preset": "three_level",
    "image_size": 1024, "guidance_scale": 7.5, "num_inference_steps": 50,
    "unet": THREE_LEVEL,
    "text_encoder": [
        SD14["text_encoder"],
        {"arch": "clip", "vocab_size": 49408, "hidden_size": 1280,
         "num_hidden_layers": 32, "num_attention_heads": 20,
         "attention_inner_dim": 1280, "max_position_embeddings": 77, "ff_mult": 4,
         "hidden_act": "gelu", "causal": True, "qkv_bias": True,
         "projection_dim": 1280}],
    "vae": dict(SD14["vae"], scaling_factor=0.13025),
    "scheduler": SD14["scheduler"],
}


@pytest.fixture
def standin(monkeypatch):
    monkeypatch.setitem(presets.PRESET_CONFIGS, "three_level", STANDIN)
    return copy.deepcopy(STANDIN_FILE)


def test_the_matching_file_is_accepted_and_counted(standin):
    """One file, both readers: the builder takes it for the preset, and the
    count gives the hand count of ``test_benchmark_flops.py``."""
    assert pipeline.program_config(standin) is STANDIN
    assert standin["unet"] == THREE_LEVEL
    assert flops.unet_forward_flops(standin["unet"]) == 6_724_783_370_240
    assert len(flops.self_site_names(standin["unet"])) == 70
    towers = standin["text_encoder"]
    assert flops.text_encoder_flops(towers) == sum(map(flops.text_encoder_flops, towers)) \
        == 13_298_503_680 + flops.text_encoder_flops(towers[1])
    # the benchmark's edit: 200 rows, 4 prompts through both towers, 2 decodes
    assert 1.36e15 < flops.work_flops(standin, 200, 0, 4, 2) < 1.37e15


def _one_depth_changed(c):
    c["unet"]["transformer_depth"][2] = 9


def _depth_as_the_scalar(c):
    c["unet"]["transformer_depth"] = 10


def _a_tower_dropped(c):
    c["text_encoder"] = c["text_encoder"][:1]


def _one_tower_not_in_a_list(c):
    c["text_encoder"] = c["text_encoder"][0]


def _towers_swapped(c):
    c["text_encoder"] = c["text_encoder"][::-1]


def _no_added_embedding_in_the_file(c):
    del c["unet"]["addition_embed_in"]


def _no_projection_in_the_file(c):
    del c["text_encoder"][1]["projection_dim"]


def _a_projection_the_preset_lacks(c):
    c["text_encoder"][0]["projection_dim"] = 768


@pytest.mark.parametrize("change,block", [
    (_one_depth_changed, "unet"), (_depth_as_the_scalar, "unet"),
    (_a_tower_dropped, "text_encoder"), (_one_tower_not_in_a_list, "text_encoder"),
    (_towers_swapped, "text_encoder"), (_no_added_embedding_in_the_file, "unet"),
    (_no_projection_in_the_file, "text_encoder"),
    (_a_projection_the_preset_lacks, "text_encoder")],
    ids=lambda v: v.__name__.strip("_") if callable(v) else v)
def test_each_single_mismatch_is_refused_by_its_block(standin, change, block):
    change(standin)
    with pytest.raises(ValueError, match=f"'three_level': {block} is"):
        pipeline.program_config(standin)


def test_an_added_embedding_only_in_the_file_is_refused(monkeypatch):
    """The other side of the same key: a preset without the attribute, or
    with None under it, shows no key, and a file that states one is refused."""
    config = copy.deepcopy(SD14)
    config["unet"]["addition_embed_in"] = 2816
    with pytest.raises(ValueError, match="'sd14': unet is"):
        pipeline.program_config(config)
    none = dataclasses.replace(presets.SD14, unet=With(presets.SD14_UNET, addition_embed_in=None))
    monkeypatch.setitem(presets.PRESET_CONFIGS, "sd14", none)
    assert pipeline.program_config(copy.deepcopy(SD14)) is none


#: sha256 of ``json.dumps(_sizes_of_program(preset), sort_keys=True)`` as the
#: parent's ``lib/pipeline.py`` gave it (PR 33's tree, before the family).
SIZES_OF_THE_PARENT = {
    "sd14": "ea6895d1d96bf37feb1220e8a556269d7ef1ef0705d6f6e7771600015dfb6ccb",
    "sd21": "987bcb82f98e2a61abd4050d6e80bca8515300195fab97ff92621b82a018a004",
    "sd21base": "794fe71279424a8c724d646b622ad51c10ebf318143c75d53e9bc9d2b0a73aab",
    "ldm256": "024a9b208707ba1cf70a26d45fe35e638b592595e8e7e8bbdd5f749b452d9ff8",
    "tiny": "205e69001c4ebdba7695ccc652bc5c3a6f6f9f91817abc03a0be7be3302ad428",
    "tiny_ldm": "7de242fe545dae77abf2b60de31f20d915635a0a25d04bef15e8bf92859420d3",
    "tiny_v": "bfd2f13cc7c7f26b123cdccfce598b4aa37cfd6da487ceb2d0a6cda821c3ff81",
}


@pytest.mark.parametrize("preset", sorted(SIZES_OF_THE_PARENT))
def test_presets_of_today_map_as_the_parent_mapped_them(preset):
    sizes = pipeline._sizes_of_program(presets.PRESET_CONFIGS[preset])
    said = hashlib.sha256(json.dumps(sizes, sort_keys=True).encode()).hexdigest()
    assert said == SIZES_OF_THE_PARENT[preset]
    assert isinstance(sizes["text_encoder"], dict)
    assert not {"addition_embed_in", "projection_dim"} & (
        set(sizes["unet"]) | set(sizes["text_encoder"]))


# -- towers: shapes, weights, tokenizer, controller --------------------------

TOY_TOWERS = (presets.TINY_TEXT, dataclasses.replace(presets.TINY_TEXT, hidden_dim=48,
                                                    num_layers=3, num_heads=3))
TINY_FILE = harness.load_json(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "rehearsal", "bench", "configs", "tiny.json"))


def _build_recorded(monkeypatch, text):
    """``pipeline.build`` of ``tiny`` with ``text`` for its towers, the
    program's ``Pipeline`` replaced by a recorder of what it is handed."""
    from p2p_tpu.engine import sampler

    pc = dataclasses.replace(presets.TINY, text=text)
    monkeypatch.setitem(presets.PRESET_CONFIGS, "tiny", pc)
    monkeypatch.setattr(sampler, "Pipeline", lambda **kw: SimpleNamespace(**kw))
    config = copy.deepcopy(TINY_FILE)
    config["text_encoder"] = pipeline._sizes_of_program(pc)["text_encoder"]
    return pipeline.build(config, SEED)


def test_two_towers_are_handed_over_as_a_list_of_two_trees_in_order(monkeypatch):
    pipe, made = _build_recorded(monkeypatch, TOY_TOWERS)
    assert pipe.text_params is made["text"] and isinstance(made["text"], list)
    assert [t["token_embed"].shape for t in made["text"]] == [(49408, 32), (49408, 48)]
    assert [len(t["layers"]) for t in made["text"]] == [2, 3]
    assert (pipe.tokenizer.vocab_size, pipe.tokenizer.model_max_length) == (49408, 16)
    assert pipe.unet_params is made["unet"] and pipe.vae_params is made["vae"]
    shapes = pipeline.weight_shapes(pipe.config)
    assert jax.tree.structure(shapes) == jax.tree.structure(made)


def test_one_tower_is_handed_over_as_the_tree_itself(monkeypatch):
    pipe, made = _build_recorded(monkeypatch, presets.TINY_TEXT)
    assert pipe.text_params is made["text"] and isinstance(made["text"], dict)
    assert _digest(made) == TINY_OF_THE_PARENT


@pytest.mark.parametrize("other", [
    dataclasses.replace(presets.TINY_TEXT, vocab_size=30522),
    dataclasses.replace(presets.TINY_TEXT, max_length=32)],
    ids=("vocabulary", "positions"))
def test_towers_of_two_tokenizers_raise(monkeypatch, other):
    with pytest.raises(ValueError, match="one tokenizer"):
        _build_recorded(monkeypatch, (presets.TINY_TEXT, other))


def test_the_controller_asks_the_tokenizer_not_the_towers():
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    edit = harness.load_json(os.path.join(harness.HERE, "traffic", "sd14.edit-replace.json"))["edit"]
    pipe = SimpleNamespace(tokenizer=HashWordTokenizer(vocab_size=49408, model_max_length=24),
                           config=SimpleNamespace(text=TOY_TOWERS))
    ctrl = window.controller(pipe, edit, "replace", ("a cat on a mat", "a dog on a mat"))
    assert ctrl.edit.mapper.shape == (1, 24, 24)


# -- the fill ----------------------------------------------------------------

#: sha256 over the leaves of ``make_weights(2**31 + 11, tiny's shapes, 3.0)``
#: as the parent's ``lib/weights.py`` filled them on the CPU (PR 33's tree).
TINY_OF_THE_PARENT = "66c6bf95bcddcff006d2655fc73e88a29440e0aebacf2e9cda49667b0dbb6929"


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _tiny_shapes():
    return pipeline.weight_shapes(presets.TINY)


def test_a_tree_of_f32_leaves_is_the_parents_bit_for_bit():
    assert TINY_FILE["assumed"]["attention_logit_gain"] == 3.0
    assert _digest(weights.make_weights(SEED, _tiny_shapes(), 3.0)) == TINY_OF_THE_PARENT


@pytest.mark.parametrize("calls", (2, 3, 7))
def test_the_leaves_do_not_depend_on_the_cap(monkeypatch, calls):
    shapes = _tiny_shapes()
    draws = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    monkeypatch.setattr(weights, "FILL_BYTES", draws // calls)
    filled, fill = [], weights._fill

    def recorded(key, stacks, order):
        drawn = sorted({at for at, _ in order})
        assert drawn == list(range(drawn[0], drawn[-1] + 1))
        filled.append((drawn[0], drawn[-1] + 1))
        return fill(key, stacks, order)

    monkeypatch.setattr(weights, "_fill", recorded)
    assert _digest(weights.make_weights(SEED, shapes, 3.0)) == TINY_OF_THE_PARENT
    assert len(filled) >= 2 and (filled[0][0], filled[-1][1]) == (0, 54)
    assert all(a[1] == b[0] for a, b in zip(filled, filled[1:]))     # in order, no gap


def test_parts_hold_the_cap_and_one_tree_of_today_is_one_call(monkeypatch):
    stack = lambda n: ((1 << 20,), "float32", "uniform", 1.0, 0.0, n)      # n x 4 MiB
    stacks = (stack(3), stack(8), stack(1), stack(2), stack(2))
    monkeypatch.setattr(weights, "FILL_BYTES", 32 << 20)
    assert weights._parts(stacks) == [(0, 1), (1, 2), (2, 5)]
    monkeypatch.setattr(weights, "FILL_BYTES", 16 << 20)
    assert weights._parts(stacks) == [(0, 1), (1, 2), (2, 4), (4, 5)]   # a stack over the cap alone
    monkeypatch.undo()
    assert weights._parts(stacks) == [(0, 5)] and weights.FILL_BYTES == 6 << 30


def _leaf(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_leaves_come_back_in_their_types_the_narrow_one_rounded():
    """Two kernels of one shape, one float32 and one bfloat16, come back in
    their types. Against the same tree in float32 alone, which is stack for
    stack the same draws, a float32 leaf agrees bit for bit and a bfloat16
    leaf is the float32 draw rounded."""
    mixed = {"a": {"kernel": _leaf((24, 16), jnp.float32)},
             "b": {"kernel": _leaf((24, 16), jnp.bfloat16)}}
    got = weights.make_weights(SEED, mixed, 3.0)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), mixed)
    for narrow in ("kernel", "bias"):
        tree = {"kernel": _leaf((24, 16), jnp.float32), "bias": _leaf((16,), jnp.float32)}
        wide = weights.make_weights(SEED, {"a": tree}, 3.0)["a"]
        tree[narrow] = _leaf(tree[narrow].shape, jnp.bfloat16)
        made = weights.make_weights(SEED, {"a": tree}, 3.0)["a"]
        for name in tree:
            assert made[name].dtype == tree[name].dtype
            np.testing.assert_array_equal(made[name], wide[name].astype(tree[name].dtype))
        assert not np.array_equal(made[narrow].astype(jnp.float32), wide[narrow])
