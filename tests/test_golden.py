"""Golden fixed-seed regression checks for the five BASELINE.json configs
(tiny CPU stand-ins, random weights).

Locks end-to-end numerics so performance work can't silently change
outputs. Two layers, so the suite stays strict on the pinning host but does
not false-fail on a different BLAS/ISA:

1. sha256 of the uint8 image bytes vs a pinned value — exact, fast.
2. On hash mismatch, tolerance comparison against the stored uint8 arrays in
   ``tests/golden/*.npz``: cross-platform float accumulation differences
   surface as ±1–2 uint8 steps on a few pixels, a regression as large or
   widespread drift. Bounds: max abs diff ≤ 3, mean abs diff ≤ 0.5.

If a change is *intentional* (e.g. a scheduler fix), regenerate both layers:
``P2P_REGEN_GOLDEN=1 pytest tests/test_golden.py`` rewrites the .npz files
and prints the new hashes to pin in GOLDEN.
"""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.controllers import factory
from p2p_tpu.engine.sampler import Pipeline, encode_prompts, text2image
from p2p_tpu.models import TINY, TINY_LDM, init_text_encoder, init_unet
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.utils.tokenizer import HashWordTokenizer

STEPS = 3
PROMPTS = ["a squirrel eating a burger", "a squirrel eating a lasagna"]


def _sha(img) -> str:
    return hashlib.sha256(np.asarray(img).tobytes()).hexdigest()[:16]


def _pipe(cfg):
    tok = HashWordTokenizer(vocab_size=cfg.text.vocab_size,
                            model_max_length=cfg.text.max_length)
    return Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )


@pytest.fixture(scope="module")
def tiny():
    return _pipe(TINY)


def _case_replace(tiny):
    """BASELINE 1: AttentionReplace 2-prompt edit, DDIM."""
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tiny.tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)
    img, _, _ = text2image(tiny, PROMPTS, ctrl, num_steps=STEPS,
                           rng=jax.random.PRNGKey(42))
    return img


def _case_refine_blend(tiny):
    """BASELINE 2: AttentionRefine + LocalBlend."""
    prompts = ["a cat on a mat", "a fluffy cat on a mat"]
    lb = factory.local_blend(prompts, ["cat", "cat"], tiny.tokenizer,
                             num_steps=STEPS, resolution=8,
                             max_len=TINY.text.max_length)
    ctrl = factory.attention_refine(
        prompts, STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tiny.tokenizer, local_blend=lb, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)
    img, _, _ = text2image(tiny, prompts, ctrl, num_steps=STEPS,
                           rng=jax.random.PRNGKey(43))
    return img


def _case_reweight_sweep(tiny):
    """BASELINE 3: AttentionReweight equalizer sweep, 4 groups via dp sweep."""
    from p2p_tpu.align.words import get_equalizer
    from p2p_tpu.parallel import make_mesh, seed_latents, sweep

    prompts = ["a smiling rabbit doll", "a smiling rabbit doll"]
    ctrls = []
    for scale in (0.5, 1.0, 2.0, 4.0):
        eq = get_equalizer(prompts[1], ("smiling",), (scale,), tiny.tokenizer)
        ctrls.append(factory.attention_reweight(
            prompts, STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
            equalizer=eq, tokenizer=tiny.tokenizer, self_max_pixels=8 * 8,
            max_len=TINY.text.max_length))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls)
    cond = encode_prompts(tiny, prompts)
    uncond = encode_prompts(tiny, [""] * len(prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)
    ctx = jnp.broadcast_to(ctx[None], (4,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(44), 4, len(prompts),
                        tiny.latent_shape)
    mesh = make_mesh(min(4, len(jax.devices("cpu"))), tp=1)
    images, _ = sweep(tiny, ctx, lats, stacked, num_steps=STEPS, mesh=mesh)
    return images


def _case_nulltext(tiny):
    """BASELINE 4: null-text inversion + replace edit replay."""
    from p2p_tpu.engine.inversion import invert

    rng = np.random.RandomState(7)
    image = (rng.rand(TINY.image_size, TINY.image_size, 3) * 255).astype(np.uint8)
    art = invert(tiny, image, "a cat on a mat", num_steps=STEPS,
                 num_inner_steps=2)
    prompts = ["a cat on a mat", "a dog on a mat"]
    ctrl = factory.attention_replace(
        prompts, STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tiny.tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)
    img, _, _ = text2image(
        tiny, prompts, ctrl, num_steps=STEPS,
        latent=jnp.asarray(art.x_t),
        uncond_embeddings=jnp.asarray(art.uncond_embeddings))
    return img


def _case_ldm(tiny):
    """BASELINE 5: LDM backend, batch of prompts, PLMS-free guidance 5."""
    pipe = _pipe(TINY_LDM)
    prompts = ["a painting of a virus monster playing guitar"] * 2
    img, _, _ = text2image(pipe, prompts, None, num_steps=STEPS,
                           rng=jax.random.PRNGKey(45))
    return img


def _case_dpm(tiny):
    """The quality-matched operating point (the DPM-Solver++(2M)
    secondary): same Replace edit, dpm multistep scheduler."""
    ctrl = factory.attention_replace(
        PROMPTS, STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tiny.tokenizer, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length)
    img, _, _ = text2image(tiny, PROMPTS, ctrl, num_steps=STEPS,
                           scheduler="dpm", rng=jax.random.PRNGKey(46))
    return img


GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

# Pinned on CPU (x86-64, f32) under JAX 0.9.0, whose default PRNG is the
# partitionable threefry: the random streams (init weights, start latents)
# differ from the older default, so both layers were regenerated together
# with P2P_REGEN_GOLDEN=1 (see module docstring). The same six cases pass
# against the previous pins with JAX_THREEFRY_PARTITIONABLE=0 in the
# environment, i.e. the re-pin encodes the PRNG default and nothing else.
GOLDEN = {
    "replace": "8dde9c1a8d9430af",
    "refine_blend": "60db370a6ca56bea",
    "reweight_sweep": "0b45bfcc134a7dda",
    "nulltext": "2bb2980052c44f63",
    "ldm": "78f4e49b5a2cb362",
    "dpm": "93136b89310fc4d9",
}

CASES = {
    "replace": _case_replace,
    "refine_blend": _case_refine_blend,
    "reweight_sweep": _case_reweight_sweep,
    "nulltext": _case_nulltext,
    "ldm": _case_ldm,
    "dpm": _case_dpm,
}


@pytest.mark.parametrize("name", list(CASES))
def test_golden_hash(tiny, name):
    img = np.asarray(CASES[name](tiny))
    path = os.path.join(GOLDEN_DIR, f"{name}.npz")

    if os.environ.get("P2P_REGEN_GOLDEN"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        np.savez_compressed(path, image=img)
        pytest.fail(f"regenerated {path}; pin GOLDEN[{name!r}] = {_sha(img)!r}")

    got = _sha(img)
    want = GOLDEN[name]
    if want == "PENDING":
        pytest.fail(f"golden hash for {name!r} not pinned yet; actual: {got}")
    if got == want:
        return
    # Hash differs — on a different BLAS/ISA that can be benign ±1-step
    # quantization drift. Fall back to tolerance against the stored array.
    if not os.path.exists(path):
        pytest.fail(
            f"golden mismatch for {name!r}: got {got}, pinned {want}, and no "
            f"stored array at {path} for tolerance fallback. If this numerics "
            "change is intentional, regenerate with P2P_REGEN_GOLDEN=1")
    ref = np.load(path)["image"]
    assert ref.shape == img.shape, (
        f"golden shape changed for {name!r}: {img.shape} vs stored {ref.shape}")
    diff = np.abs(img.astype(np.int16) - ref.astype(np.int16))
    assert diff.max() <= 3 and diff.mean() <= 0.5, (
        f"golden mismatch for {name!r} beyond cross-platform tolerance: "
        f"hash {got} vs pinned {want}; max|Δ|={diff.max()}, "
        f"mean|Δ|={diff.mean():.3f}. If this numerics change is intentional, "
        "regenerate with P2P_REGEN_GOLDEN=1 and update GOLDEN")
