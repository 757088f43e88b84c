"""The benchmark's own operation counts (``benchmarks/lib/flops.py``)
against numbers worked by hand for one residual block, one self-attention
site and one cross-attention site of two geometries, and against XLA's
count of a batch-4 SD-1.4 U-Net forward."""

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
           "latent32": LATENT32}

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
