"""The benchmark's own operation counts (``benchmarks/lib/flops.py``)
against numbers worked by hand for one residual block, one self-attention
site and one cross-attention site of two geometries, against XLA's count of
a batch-4 SD-1.4 U-Net forward, to the unit against what the count gave both
cells before it learnt the family (PR 33), and against a hand count of a
three-level member whose depth differs by level."""

import os

import pytest

from benchmarks.lib import flops, harness

#: A second geometry for the counts, that of the program's ``ldm256`` preset
#: (32 x 32 latent, 1280-wide context, VQ decoder). No cell runs it: the
#: preset is not the published model (PERF.md, Open questions).
LATENT32 = {
    "unet": {"sample_size": 32, "context_len": 77, "cross_attention_dim": 1280},
    "vae": {"kind": "vq", "in_channels": 3, "latent_channels": 4, "base_channels": 128,
            "channel_mults": [1, 2, 2, 4], "layers_per_block": 2, "num_codebook": 16384},
}
CONFIGS = {"sd14": harness.load_json(os.path.join(harness.HERE, "configs", "sd14.json")),
           "sd21": harness.load_json(os.path.join(harness.HERE, "configs", "sd21.json")),
           "latent32": LATENT32}
#: A member of the same U-Net family that no cell runs and no file states:
#: 128 x 128 latent, three levels of which the first has no attention, 0 / 2 /
#: 10 transformer blocks by level (10 in the mid block), heads of 64, a
#: context of 77 x 2048 from two text towers, and a 2816-wide vector embedded
#: beside the time step.
THREE_LEVEL = {
    "sample_size": 128, "in_channels": 4, "out_channels": 4,
    "block_out_channels": [320, 640, 1280], "attention_levels": [False, True, True],
    "layers_per_block": 2, "transformer_depth": [0, 2, 10],
    "num_attention_heads": None, "attention_head_size": 64,
    "cross_attention_dim": 2048, "context_len": 77, "norm_num_groups": 32,
    "ff_mult": 4, "addition_embed_in": 2816}

# A 3x3 convolution padded by 1 onto n x n pixels has (3n - 2)^2 taps that do
# not fall on the padding: 190^2 at 64 x 64, 94^2 at 32 x 32.
HAND = {
    # two convs 320 -> 320 and the time projection 1280 -> 320
    ("sd14", "res"): 2 * (2 * 190 ** 2 * 320 * 320) + 2 * 1280 * 320,
    ("latent32", "res"): 2 * (2 * 94 ** 2 * 320 * 320) + 2 * 1280 * 320,
    # q, k, v, out: 8 P C^2; Q K^T and P V: 4 P^2 C
    ("sd14", "self"): 3_355_443_200 + 21_474_836_480,
    ("latent32", "self"): 838_860_800 + 1_342_177_280,
    # q, out: 4 P C^2; k, v: 4 L D C; the two products: 4 P L C
    ("sd14", "cross"): 1_677_721_600 + 75_694_080 + 403_701_760,
    ("latent32", "cross"): 419_430_400 + 126_156_800 + 100_925_440,
}


@pytest.mark.parametrize("name,part", sorted(HAND))
def test_against_hand_worked_numbers(name, part):
    uc = CONFIGS[name]["unet"]
    pixels = uc["sample_size"] ** 2
    got = {
        "res": lambda: flops.res_block_flops(pixels, 320, 320, 1280),
        "self": lambda: flops.self_attention_flops(pixels, 320),
        "cross": lambda: flops.cross_attention_flops(
            pixels, 320, uc["context_len"], uc["cross_attention_dim"]),
    }[part]()
    assert got == HAND[(name, part)]


def test_against_xla_count_of_a_batch4_sd14_unet_forward():
    """XLA counts 3.158 TFLOP for the lowered (not yet optimised) batch-4
    U-Net forward of the current tree (``Lowered.cost_analysis()``, PR 26, on
    the CPU): matrix products and convolutions as counted here, plus about 1 %
    of softmax, norm and activation arithmetic that this count leaves out on
    purpose. PERF.md's older 3.63 TFLOP was XLA's count of the *compiled*
    program of the old setup, which adds the fused element-wise work and the
    bf16 casts; it is the upper mark."""
    mine = 4 * flops.unet_forward_flops(CONFIGS["sd14"]["unet"])
    assert 0.985 < mine / 3.158493888512e12 < 1.0
    assert 0.85 < mine / 3.63e12 < 1.0


def test_cached_cross_attention_costs_less_and_decode_counts_the_codebook():
    uc = CONFIGS["sd14"]["unet"]
    full, cached = flops.unet_forward_flops(uc), flops.unet_forward_flops(uc, cross=False)
    sites = flops.unet_sites(uc)
    assert len(sites) == 16 and sites[0] == ("down", 0, 4096, 320)
    cross = sum(flops.cross_attention_flops(p, c, 77, 768) for _, _, p, c in sites)
    assert full - cached == cross
    kl = dict(CONFIGS["latent32"]["vae"], kind="kl")
    assert (flops.decode_flops(CONFIGS["latent32"]["vae"], 32)
            - flops.decode_flops(kl, 32)) == 2 * 1024 * 4 * 16384


# -- to the unit: both cells as before PR 33, and the family ------------------

@pytest.mark.parametrize("name,what,want", [
    ("sd14", "unet", 781_756_723_200), ("sd21", "unet", 2_116_596_674_560),
    ("sd14", "unet_cached", 749_661_777_920), ("sd21", "unet_cached", 2_047_089_111_040),
    ("sd14", "text", 13_298_503_680), ("sd21", "text", 45_127_233_536),
    ("sd14", "decode", 2_498_246_495_232), ("sd21", "decode", 5_729_874_267_136),
    # one call of the cell: 200 full rows, 4 prompts, 2 images
    ("sd14", "call", 161_401_031_645_184), ("sd21", "call", 434_959_592_380_416)])
def test_both_cells_count_what_they_counted(name, what, want):
    """The integers of the parent's ``flops.py`` (PR 32), which generalising
    the count may not move: ``model.step_mfu_pct`` is the same number on the
    same run."""
    c = CONFIGS[name]
    got = {"unet": lambda: flops.unet_forward_flops(c["unet"]),
           "unet_cached": lambda: flops.unet_forward_flops(c["unet"], cross=False),
           "text": lambda: flops.text_encoder_flops(c["text_encoder"]),
           "decode": lambda: flops.decode_flops(c["vae"], c["unet"]["sample_size"]),
           "call": lambda: flops.work_flops(c, 200, 0, 4, 2)}[what]()
    assert got == want


def _transformers(uc) -> int:
    """Every spatial transformer of a U-Net, one call a group of equal ones."""
    side, chs = uc["sample_size"], uc["block_out_channels"]
    per_level = 2 * uc["layers_per_block"] + 1           # down and up
    f = sum(per_level * flops.transformer_flops(uc, (side >> lvl) ** 2, c, True,
                                                flops.depth_at(uc, lvl))
            for lvl, c in enumerate(chs))
    last = len(chs) - 1
    return f + flops.transformer_flops(uc, (side >> last) ** 2, chs[last], True,
                                       flops.mid_depth(uc))


@pytest.mark.parametrize("what,want", [
    ("transformers", 5_138_074_828_800), ("everything_else", 1_586_698_055_680),
    ("added_embedding", 10_485_760), ("row", 6_724_783_370_240)])
def test_three_level_member_against_the_hand_count(what, want):
    uc = THREE_LEVEL
    row = flops.unet_forward_flops(uc)
    added = row - flops.unet_forward_flops({k: v for k, v in uc.items()
                                            if k != "addition_embed_in"})
    got = {"transformers": _transformers(uc), "added_embedding": added, "row": row,
           "everything_else": row - _transformers(uc) - added}[what]
    assert got == want
    assert added == 2 * 2816 * 1280 + 2 * 1280 * 1280      # in -> 4 C0 -> 4 C0


@pytest.mark.parametrize("name", ("sd14", "sd21"))
def test_depth_as_a_list_and_towers_as_a_list_say_the_same(name):
    """``transformer_depth`` 1 is [1, 1, 1, 1]: the last level's entry is the
    mid block's, its own blocks have no attention (``attention_levels``); one
    tower is a list of one, two towers are summed; a level of depth 0 counts
    no transformer whatever ``attention_levels`` says."""
    uc, tc = CONFIGS[name]["unet"], CONFIGS[name]["text_encoder"]
    as_list = dict(uc, transformer_depth=[1, 1, 1, 1])
    assert flops.unet_forward_flops(as_list) == flops.unet_forward_flops(uc)
    assert flops.unet_sites(as_list) == flops.unet_sites(uc)
    assert [flops.depth_at(as_list, lvl) for lvl in range(4)] == [1, 1, 1, 0]
    assert flops.mid_depth(as_list) == flops.mid_depth(uc) == 1
    none_on_top = dict(uc, transformer_depth=[0, 1, 1, 1])
    assert [s for s in flops.unet_sites(uc) if s[1] != 0] == flops.unet_sites(none_on_top)
    assert flops.unet_sites(dict(uc, attention_levels=[False, True, True, False])) \
        == flops.unet_sites(none_on_top)
    deeper = dict(uc, transformer_depth=2)
    assert len(flops.unet_sites(deeper)) == 32
    assert flops.unet_forward_flops(deeper) > flops.unet_forward_flops(uc)
    assert flops.text_encoder_flops([tc]) == flops.text_encoder_flops(tc)
    assert flops.text_encoder_flops([tc, tc]) == 2 * flops.text_encoder_flops(tc)
    two = dict(CONFIGS[name], text_encoder=[tc, CONFIGS["sd14"]["text_encoder"]])
    assert (flops.work_flops(two, 0, 0, 4, 0)
            == 4 * (flops.text_encoder_flops(tc) + 13_298_503_680))
