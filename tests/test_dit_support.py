"""What `pixart_sigma` (PixArt-Sigma at 1024², a transformer denoiser with
adaLN-single and a learned variance, T5's encoder over a masked caption)
asks of the program, at the toy preset `tiny_dit` on the CPU with the
benchmark's seeded weights: the denoiser's forward and the T5 tower against
the plain reference (``benchmarks/reference/pixart_sigma.py``, imported by
path), a ``text2image`` replace edit and a ``sweep`` of it against the
reference's whole edit, the caption's key mask, the learned variance
dropped, the layout of 56 sites, and ``p2p-tpu edit``.

Tolerances: both sides compute in float32 on the CPU from the same bfloat16
kernels, each widened where it is used, so they differ by the order of
their sums alone. A forward reads a relative difference of a few 1e-7
(``FORWARD``, 1e-5, has that margin and stands three orders under the
bfloat16-arrays forward, tested); an edit's uint8 images read 0 to a few
1e-6 (``IMAGE``, 2e-4, as `tiny_xl`'s, for the same reasons)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import check as check_mod
from benchmarks.lib import harness, pipeline
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.base import controller_touches
from p2p_tpu.engine import inversion
from p2p_tpu.engine.sampler import encode_prompts, text2image
from p2p_tpu.models import apply_transformer, transformer_layout
from p2p_tpu.models.conditioning import Caption
from p2p_tpu.models.config import PIXART_SIGMA, TINY_DIT
from p2p_tpu.models.text_encoder import apply_text_encoder
from p2p_tpu.obs import launches
from p2p_tpu.parallel.sweep import sweep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = ("a red fox eating a burger in the forest",
           "a red cat eating a burger in the forest")
KEY = (20261019, 42)
EDIT = {"kind": "replace", "num_steps": 4, "guidance_scale": 4.5,
        "cross_replace_steps": 0.8, "self_replace_steps": 0.5,
        "self_max_pixels": 64}
SEED = 2 ** 31 + 4242
#: Relative RMS difference of a forward (see the module's docstring).
FORWARD = 1e-5
#: ``image_rel_err`` of an edit's images (see the module's docstring).
IMAGE = 2e-4


@pytest.fixture(scope="module")
def tiny_dit():
    """The toy preset built as the benchmark builds a cell, its weights
    filled from a seed, and the plain reference."""
    config = dict(pipeline._sizes_of_program(TINY_DIT), name="tiny_dit",
                  preset="tiny_dit", assumed={"attention_logit_gain": 3.0})
    pipe, weights = pipeline.build(config, SEED)
    return config, pipe, weights, harness.load_module("reference", "pixart_sigma")


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def _caption(tiny_dit, prompts=PROMPTS):
    config, pipe, weights, ref = tiny_dit
    ids = jnp.asarray(np.stack([ref.token_ids(config, p) for p in prompts]))
    return ids, ref.key_mask(ids)


def _controller(pipe, self_max_pixels=EDIT["self_max_pixels"]):
    return factory.attention_replace(
        list(PROMPTS), EDIT["num_steps"], EDIT["cross_replace_steps"],
        EDIT["self_replace_steps"], pipe.tokenizer,
        self_max_pixels=self_max_pixels,
        max_len=pipe.tokenizer.model_max_length, store=True)


@pytest.fixture(scope="module")
def reference_edit(tiny_dit):
    """The reference's images of the edit, once for the tests that need them."""
    config, pipe, weights, ref = tiny_dit
    x_T = ref.noise(KEY, (1,) + pipe.latent_shape)
    align = {k: jnp.asarray(v) for k, v in ref.alignment(config, EDIT, PROMPTS).items()}
    img, _ = ref.make_edit_fn(config, EDIT)(
        weights, x_T, jnp.asarray(ref.prompt_ids(config, PROMPTS)), align)
    return np.asarray(ref.to_uint8(img))


def _err(served, reference):
    return max(check_mod.image_rel_err(served[j], reference[j])
               for j in range(len(reference)))


# -- the parts against the plain reference -------------------------------------

def test_t5_tower_matches_the_reference(tiny_dit):
    config, pipe, weights, ref = tiny_dit
    ids, mask = _caption(tiny_dit)
    ours = jax.jit(apply_text_encoder, static_argnums=1)(
        pipe.text_params, TINY_DIT.text, ids, mask=mask.astype(jnp.int32))
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(lambda w, i, m: ref.t5_encoder(w, config["text_encoder"], i, m))(
            weights["text"], ids, mask)
    assert ours.shape == (2, 16, 32) and _rel(ours, theirs) < FORWARD
    with pytest.raises(ValueError, match="key mask"):
        apply_text_encoder(pipe.text_params, TINY_DIT.text, ids)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_denoiser_matches_the_reference_forward(tiny_dit, dtype):
    """The forward in float32 arrays is within ``FORWARD``; the same forward
    in bfloat16 arrays is not, by orders: the limit can see the precision."""
    config, pipe, weights, ref = tiny_dit
    ids, mask = _caption(tiny_dit)
    caption = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 4))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: ref.denoiser(*a[:1], config["transformer"], *a[1:]))(
            weights["unet"], x, jnp.int32(700), caption, mask)
    cast = lambda a: a.astype(dtype)                                # noqa: E731
    eps, state = jax.jit(apply_transformer, static_argnums=1)(
        pipe.unet_params, TINY_DIT.unet, cast(x), jnp.int32(700),
        Caption(cast(caption), mask.astype(jnp.int32)))
    assert eps.shape == x.shape and state == ()
    err = _rel(eps.astype(jnp.float32), want)
    if dtype == jnp.float32:
        assert err < FORWARD
    else:
        assert err > 100 * FORWARD


def test_a_replace_edit_matches_the_reference(tiny_dit, reference_edit):
    _, pipe, _, _ = tiny_dit
    reference = reference_edit
    images, _, _ = text2image(
        pipe, list(PROMPTS), _controller(pipe), num_steps=EDIT["num_steps"],
        guidance_scale=EDIT["guidance_scale"], rng=jnp.asarray(KEY, jnp.uint32))
    assert _err(np.asarray(images), reference) < IMAGE
    launch = launches.programs("jit__text2image_jit")[-1]
    assert launch.denoiser == "transformer" and launch.caption_len == 16
    # BOS, 9 words, EOS: 11 live keys a prompt, 2 for the empty one
    assert launch.caption_keys == (2, 2, 11, 11)
    assert launch.self_window_sites == 2 == len(launch.self_sites)
    # bfloat16 arrays fail the limit by orders
    images, _, _ = text2image(
        pipe, list(PROMPTS), _controller(pipe), num_steps=EDIT["num_steps"],
        guidance_scale=EDIT["guidance_scale"], rng=jnp.asarray(KEY, jnp.uint32),
        dtype=jnp.bfloat16)
    assert _err(np.asarray(images), reference) > 100 * IMAGE


def test_a_sweep_of_the_edit_matches_the_reference(tiny_dit, reference_edit):
    """One vmapped program over two groups of the same edit and noise: each
    group is the reference's images (a uint8 level apart at most: another
    batch width sums in another order)."""
    _, pipe, _, ref = tiny_dit
    reference = reference_edit
    ctrl = _controller(pipe)
    cond, uncond = encode_prompts(pipe, list(PROMPTS)), encode_prompts(pipe, [""] * 2)
    assert isinstance(cond, Caption) and cond.mask.dtype == jnp.int32
    one = jax.tree.map(lambda u, c: jnp.concatenate([u, c]), uncond, cond)
    ctx = jax.tree.map(lambda a: jnp.stack([a, a]), one)
    x_T = ref.noise(KEY, (1,) + pipe.latent_shape)
    lats = jnp.stack([jnp.broadcast_to(x_T, (2,) + pipe.latent_shape)] * 2)
    images, _ = sweep(pipe, ctx, lats, jax.tree.map(lambda x: jnp.stack([x, x]), ctrl),
                      num_steps=EDIT["num_steps"], guidance_scale=EDIT["guidance_scale"])
    for g in range(2):
        assert _err(np.asarray(images[g]), reference) < IMAGE


# -- the caption's key mask and the learned variance ----------------------------

def test_tokens_past_the_first_end_of_text_change_nothing(tiny_dit):
    """Other ids in the padding: the tower's live rows and the denoiser's
    output are the same, to the last bit; another live token is not."""
    _, pipe, _, _ = tiny_dit
    ids, mask = _caption(tiny_dit)
    mask = mask.astype(jnp.int32)
    other = jnp.where(mask > 0, ids, 1000 + jnp.arange(16))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 4))

    @jax.jit
    def run(ids):
        h = apply_text_encoder(pipe.text_params, TINY_DIT.text, ids, mask=mask)
        eps, _ = apply_transformer(pipe.unet_params, TINY_DIT.unet, x,
                                   jnp.int32(500), Caption(h, mask))
        return h, eps

    (h0, e0), (h1, e1) = run(ids), run(other)
    live = np.asarray(mask, bool)
    np.testing.assert_array_equal(np.asarray(h0)[live], np.asarray(h1)[live])
    assert not np.array_equal(np.asarray(h0)[~live], np.asarray(h1)[~live])
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    _, e2 = run(ids.at[:, 2].set(1000))
    assert _rel(e2, e0) > 1e-3


def test_only_the_first_channels_reach_the_sampler(tiny_dit):
    """The final layer computes 8 channels a pixel; changing the weights of
    the last 4 (the learned variance) leaves ε as it is, and of the first 4
    does not."""
    _, pipe, _, _ = tiny_dit
    ids, mask = _caption(tiny_dit)
    cond = Caption(jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32)),
                   mask.astype(jnp.int32))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 16, 4))
    params = pipe.unet_params
    forward = jax.jit(apply_transformer, static_argnums=1)
    kernel = params["final"]["proj_out"]["kernel"]          # (width, 2*2*8)
    assert kernel.shape[-1] == 4 * TINY_DIT.unet.out_channels == 32
    sigma = (jnp.arange(32) % 8) >= 4                       # (p, q, channel) minor

    def eps_with(cols):
        p = jax.tree.map(lambda a: a, params)
        p["final"] = dict(p["final"], proj_out=dict(
            p["final"]["proj_out"], kernel=jnp.where(cols, kernel * 3 + 1, kernel)))
        return forward(p, TINY_DIT.unet, x, jnp.int32(10), cond)[0]

    base = forward(params, TINY_DIT.unet, x, jnp.int32(10), cond)[0]
    assert base.shape[-1] == 4
    np.testing.assert_array_equal(np.asarray(eps_with(sigma)), np.asarray(base))
    assert _rel(eps_with(~sigma), base) > 1e-2


# -- the layout, the self window and the paths a DiT does not take ---------------

def test_the_layout_has_56_sites_and_the_self_window_is_all_or_none():
    layout = transformer_layout(PIXART_SIGMA.unet)
    names = [f"{m.place}{m.layer_idx}" for m in layout.metas]
    assert names == [f"block{i}" for i in range(56)]
    assert [m.is_cross for m in layout.metas] == [False, True] * 28
    assert {(m.resolution, m.heads, m.channels) for m in layout.metas} == {(64, 16, 1152)}
    assert [m.key_len for m in layout.metas[:2]] == [4096, 300]
    assert layout.latent_size == 64 and layout.denoiser == "transformer"
    assert layout.edit_resolution() == 64 and layout.num_store_slots == 0
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    tok = HashWordTokenizer(vocab_size=32128, model_max_length=300)
    selfs = [m for m in layout.metas if not m.is_cross]
    for bound, touched in ((4096, 28), (4095, 0), (None, 28)):
        ctrl = layout.resolve(factory.attention_replace(
            list(PROMPTS), 50, 0.8, 0.4, tok, self_max_pixels=bound, max_len=300))
        assert sum(controller_touches(ctrl, m) for m in selfs) == touched
        assert all(controller_touches(ctrl, m) for m in layout.metas if m.is_cross)
    assert ctrl.edit.mapper.shape == (1, 300, 300)


def test_paths_built_on_the_unet_refuse_a_dit_by_its_kind(tiny_dit):
    _, pipe, _, _ = tiny_dit
    with pytest.raises(ValueError, match="null-text inversion: not supported "
                                         "for a transformer denoiser"):
        inversion.invert(pipe, np.zeros((64, 64, 3), np.uint8), "a cat",
                         num_steps=2)
    blend = factory.local_blend(list(PROMPTS), [["fox"], ["cat"]], pipe.tokenizer,
                                max_len=16)
    with pytest.raises(ValueError, match="LocalBlend: not supported for a "
                                         "transformer denoiser"):
        transformer_layout(TINY_DIT.unet).resolve(factory.attention_replace(
            list(PROMPTS), 4, 0.8, 0.4, pipe.tokenizer, local_blend=blend,
            max_len=16))
    from p2p_tpu.serve.request import Request, prepare

    with pytest.raises(ValueError, match="the serve engine: not supported"):
        prepare(Request(request_id="r", prompt="a cat", steps=2), pipe)
    with pytest.raises(ValueError, match="cached attention sites"):
        text2image(pipe, list(PROMPTS), None, num_steps=4, gate=2)


def test_the_cli_edits_with_the_toy_preset(tmp_path):
    from p2p_tpu.cli import main

    assert main(["edit", "--quiet", "--preset", "tiny_dit", "--source",
                 "a cat riding a bike", "--target", "a dog riding a bike",
                 "--steps", "2", "--out-dir", str(tmp_path)]) == 0
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".jpg")]) >= 2
