"""The benchmark against a transformer denoiser and an encoder-only T5 tower,
which the program does not run yet: ``lib/pipeline.py`` maps a preset whose
denoiser is a transformer over patch tokens (adaLN-single, self- and
cross-attention in every block, PixArt-Sigma's published sizes) and whose
tower has a gated feed-forward and relative positions, ``lib/flops.py``
counts both, ``lib/scopes.py`` places the transformer's scopes,
``lib/weights.py`` fills such a tree and bounds the fill by the chip, and
``lib/controls.py`` narrows only floating leaves. The stand-in is built here,
its initialiser and tower are monkeypatched in; no configuration file states
it and no cell runs it.

The same file holds the tests of where the loop comes from
(``lib/trace.py:program_loops``): membership by the compiled program's text
against nesting under the ``while`` on a CPU-lowered toy program, and the
recorded ``sd14`` trace with its ``while`` event deleted, which is read the
same where the program's text is there and refused as incomplete where it is
not."""

import copy
import dataclasses
import glob
import gzip
import hashlib
import json
import math
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import controls, flops, harness, pipeline, scopes, weights
from benchmarks.lib import trace as T
from p2p_tpu import models
from p2p_tpu.models import config as presets

from test_benchmark_family import With  # noqa: E402

SD14 = harness.load_json(os.path.join(harness.HERE, "configs", "sd14.json"))
SDXL = harness.load_json(os.path.join(harness.HERE, "configs", "sdxl.json"))
SEED = 2 ** 31 + 41
GIB = 1 << 30


@dataclasses.dataclass(frozen=True)
class DiT:
    """A transformer denoiser's configuration as the contract of
    ``lib/pipeline.py:_sizes_of_program`` reads it (PixArt-Sigma at 1024^2:
    ``PixArt-alpha/PixArt-Sigma-XL-2-1024-MS``, transformer ``config.json``)."""

    kind: str = "transformer"
    sample_size: int = 128
    patch_size: int = 2
    in_channels: int = 4
    out_channels: int = 8
    num_layers: int = 28
    num_heads: int = 16
    head_dim: int = 72
    context_dim: int = 1152
    caption_channels: int = 4096
    context_len: int = 300
    norm_type: str = "ada_norm_single"
    activation: str = "gelu-approximate"
    ff_mult: int = 4
    attention_bias: bool = True
    use_additional_conditions: bool = False
    interpolation_scale: int = 2
    kernel_dtype: str = "bfloat16"


#: T5-v1.1-XXL's encoder as the tower's configuration would state it.
T5 = With(dataclasses.replace(presets.SD14_TEXT, arch="t5", vocab_size=32128,
                              hidden_dim=4096, num_layers=24, num_heads=64,
                              max_length=300, activation="gated-gelu",
                              causal=False, attn_qkv_bias=False,
                              kernel_dtype="bfloat16"),
          intermediate_size=10240, relative_attention_num_buckets=32,
          relative_attention_max_distance=128)
STANDIN = presets.PipelineConfig(
    "pixart-sigma", DiT(), T5, presets.SDXL.vae, image_size=1024,
    guidance_scale=4.5, num_steps=20, scheduler=presets.SDXL.scheduler)

#: The file such a preset would bring, written by hand.
PIXART_SIGMA = {
    "sample_size": 128, "patch_size": 2, "in_channels": 4, "out_channels": 8,
    "num_layers": 28, "num_attention_heads": 16, "attention_head_dim": 72,
    "cross_attention_dim": 1152, "caption_channels": 4096, "context_len": 300,
    "norm_type": "ada_norm_single", "activation_fn": "gelu-approximate",
    "ff_mult": 4, "attention_bias": True, "use_additional_conditions": False,
    "interpolation_scale": 2}
T5_XXL = {
    "arch": "t5", "vocab_size": 32128, "hidden_size": 4096,
    "num_hidden_layers": 24, "num_attention_heads": 64,
    "attention_inner_dim": 4096, "max_position_embeddings": 300,
    "intermediate_size": 10240, "hidden_act": "gated-gelu", "causal": False,
    "qkv_bias": False, "relative_attention_num_buckets": 32,
    "relative_attention_max_distance": 128}
STANDIN_FILE = {
    "name": "pixart_sigma", "preset": "pixart_sigma", "image_size": 1024,
    "guidance_scale": 4.5, "num_inference_steps": 20,
    "transformer": PIXART_SIGMA, "text_encoder": T5_XXL,
    "vae": SDXL["vae"], "scheduler": SDXL["scheduler"],
    "assumed": {"attention_logit_gain": 3.0}}


def _dense(n_in, n_out, dtype, bias=True):
    out = {"kernel": jnp.zeros((n_in, n_out), dtype)}
    if bias:
        out["bias"] = jnp.zeros((n_out,), jnp.float32)
    return out


def init_transformer(key, cfg):
    """The stand-in's initialiser: the tree a PixArt-style denoiser would
    have, in the contract's names (zeros; only its shapes are read)."""
    c, kd, p = cfg.num_heads * cfg.head_dim, jnp.dtype(cfg.kernel_dtype), cfg.patch_size
    attn = lambda n_in: {"to_q": _dense(c, c, kd, cfg.attention_bias),      # noqa: E731
                         "to_k": _dense(n_in, c, kd, cfg.attention_bias),
                         "to_v": _dense(n_in, c, kd, cfg.attention_bias),
                         "to_out": _dense(c, c, kd)}
    block = lambda: {"self_attn": attn(c), "cross_attn": attn(cfg.context_dim),   # noqa: E731
                     "ff_in": _dense(c, c * cfg.ff_mult, kd),
                     "ff_out": _dense(c * cfg.ff_mult, c, kd),
                     "scale_shift_table": jnp.zeros((6, c), jnp.float32)}
    return {
        "patch_embed": {"kernel": jnp.zeros((p, p, cfg.in_channels, c), kd),
                        "bias": jnp.zeros((c,), jnp.float32)},
        "caption_proj": {"linear_1": _dense(cfg.caption_channels, c, kd),
                         "linear_2": _dense(c, cfg.context_dim, kd)},
        "time_embed": {"linear_1": _dense(256, c, kd), "linear_2": _dense(c, c, kd)},
        "t_block": _dense(c, 6 * c, kd),
        "blocks": [block() for _ in range(cfg.num_layers)],
        "final": {"scale_shift_table": jnp.zeros((2, c), jnp.float32),
                  "proj_out": _dense(c, p * p * cfg.out_channels, kd)}}


def init_t5(key, t):
    """The stand-in's T5 encoder, its layers stacked for a scan: each kernel
    is one leaf with the layers on its leading axis."""
    d, n, kd = t.hidden_dim, t.num_layers, jnp.dtype(t.kernel_dtype)
    stacked = lambda a, b: {"kernel": jnp.zeros((n, a, b), kd)}     # noqa: E731
    return {
        "token_embed": jnp.zeros((t.vocab_size, d), jnp.float32),
        "relative_attention_bias": jnp.zeros(
            (t.relative_attention_num_buckets, t.num_heads), jnp.float32),
        "layers": {"q": stacked(d, t.inner_dim), "k": stacked(d, t.inner_dim),
                   "v": stacked(d, t.inner_dim), "out": stacked(t.inner_dim, d),
                   "wi_0": stacked(d, t.intermediate_size),
                   "wi_1": stacked(d, t.intermediate_size),
                   "wo": stacked(t.intermediate_size, d),
                   "ln1": {"scale": jnp.zeros((n, d), jnp.float32)},
                   "ln2": {"scale": jnp.zeros((n, d), jnp.float32)}},
        "final_ln": {"scale": jnp.zeros((d,), jnp.float32)}}


@pytest.fixture
def standin(monkeypatch):
    """The preset registered, its initialisers in the program's place."""
    tower = models.init_text_encoder
    monkeypatch.setitem(presets.PRESET_CONFIGS, "pixart_sigma", STANDIN)
    monkeypatch.setattr(models, "init_transformer", init_transformer, raising=False)
    monkeypatch.setattr(models, "init_text_encoder",
                        lambda key, t: init_t5(key, t) if t.arch == "t5" else tower(key, t))
    return copy.deepcopy(STANDIN_FILE)


# -- the file: accepted, refused by block, counted ---------------------------

def test_the_matching_file_is_accepted_and_counted(standin):
    assert pipeline.program_config(standin) is STANDIN
    assert pipeline._sizes_of_program(STANDIN)["transformer"] == PIXART_SIGMA
    assert "unet" not in pipeline._sizes_of_program(STANDIN)
    tc = standin["transformer"]
    assert flops.transformer_block_flops(tc) == 236_767_150_080
    assert flops.transformer_forward_flops(tc) == 6_633_579_773_952 \
        == 28 * 236_767_150_080 + 4_099_571_712
    assert flops.text_encoder_flops(standin["text_encoder"]) == 2_813_696_409_600
    # a batch-4 step of 50, 4 prompts, 2 images: the denoiser's rows count
    # as the U-Net's did, and the decode takes the latent's side from its block
    call = flops.work_flops(standin, 200, 0, 4, 2)
    assert call == (200 * 6_633_579_773_952 + 4 * 2_813_696_409_600
                    + 2 * flops.decode_flops(SDXL["vae"], 128))
    names = flops.self_site_names(tc)
    assert names[:2] == ["block0", "block2"] and names[-1] == "block54" and len(names) == 28
    assert flops.self_site_names(flops.denoiser(standin)) == names


def test_the_hand_counts_of_the_parts():
    """The parts of the row as worked by hand: per block self, cross and
    feed-forward; outside the blocks patch, caption, time and final."""
    p, c = 4096, 1152
    assert flops.transformer_block_flops(PIXART_SIGMA) == (
        (8 * p * c * c + 4 * p * p * c)                          # self
        + (4 * p * c * c + 4 * 300 * 1152 * c + 4 * p * 300 * c)  # cross, all 300 keys
        + 2 * 2 * p * c * 4 * c)                                 # GELU feed-forward
    outside = (2 * p * 16 * c                                    # patch 2x2x4 -> 1152
               + 2 * 300 * (4096 * c + c * c)                    # caption projection
               + 2 * 256 * c + 2 * c * c + 2 * c * 6 * c         # time MLP, t_block
               + 2 * p * c * 32)                                 # final: 2x2x8 a token
    assert outside == 4_099_571_712
    cached = flops.transformer_forward_flops(PIXART_SIGMA, cross=False)
    assert flops.transformer_forward_flops(PIXART_SIGMA) - cached == (
        28 * (4 * p * c * c + 4 * 300 * 1152 * c + 4 * p * 300 * c)
        + 2 * 300 * (4096 * c + c * c))
    more = dict(PIXART_SIGMA, use_additional_conditions=True)
    assert flops.transformer_forward_flops(more) - flops.transformer_forward_flops(
        PIXART_SIGMA) == 3 * (2 * 256 * 384 + 2 * 384 * 384)
    geglu = dict(PIXART_SIGMA, activation_fn="geglu")
    assert flops.transformer_block_flops(geglu) - flops.transformer_block_flops(
        PIXART_SIGMA) == 2 * p * c * 4 * c


def test_a_tower_without_intermediate_size_counts_as_before():
    """A CLIP tower keeps ``ff_mult``, two matrices; ``intermediate_size``
    with an ungated activation is two matrices of that width."""
    clip = SDXL["text_encoder"][0]
    assert flops.text_encoder_flops(clip) == 13_298_503_680
    wide = dict(clip, intermediate_size=4 * 768)
    del wide["ff_mult"]
    assert flops.text_encoder_flops(wide) == 13_298_503_680
    gated = dict(T5_XXL, hidden_act="gelu")
    assert flops.text_encoder_flops(T5_XXL) - flops.text_encoder_flops(gated) == \
        24 * 2 * 300 * 4096 * 10240


def _more_layers(c):
    c["transformer"]["num_layers"] = 27


def _heads_of_64(c):
    c["transformer"]["attention_head_dim"] = 64


def _patch_of_1(c):
    c["transformer"]["patch_size"] = 1


def _learned_sigma_dropped(c):
    c["transformer"]["out_channels"] = 4


def _a_transformer_key_missing(c):
    del c["transformer"]["interpolation_scale"]


def _a_unet_block_beside(c):
    c["unet"] = SDXL["unet"]


def _a_unet_block_instead(c):
    c["unet"] = SDXL["unet"]
    del c["transformer"]


def _intermediate_changed(c):
    c["text_encoder"]["intermediate_size"] = 16384


def _ff_mult_in_place_of_intermediate(c):
    del c["text_encoder"]["intermediate_size"]
    c["text_encoder"]["ff_mult"] = 2.5


def _no_relative_buckets(c):
    del c["text_encoder"]["relative_attention_num_buckets"]


def _the_tokenizer_of_77(c):
    c["text_encoder"]["max_position_embeddings"] = 77


@pytest.mark.parametrize("change,block", [
    (_more_layers, "transformer"), (_heads_of_64, "transformer"),
    (_patch_of_1, "transformer"), (_learned_sigma_dropped, "transformer"),
    (_a_transformer_key_missing, "transformer"), (_a_unet_block_beside, "unet"),
    (_a_unet_block_instead, "transformer"), (_intermediate_changed, "text_encoder"),
    (_ff_mult_in_place_of_intermediate, "text_encoder"),
    (_no_relative_buckets, "text_encoder"), (_the_tokenizer_of_77, "text_encoder")],
    ids=lambda v: v.__name__.strip("_") if callable(v) else v)
def test_each_single_mismatch_is_refused_by_its_block(standin, change, block):
    change(standin)
    with pytest.raises(ValueError, match=f"'pixart_sigma': {block} is"):
        pipeline.program_config(standin)


def test_a_transformer_block_for_a_unet_preset_is_refused():
    config = copy.deepcopy(SDXL)
    config["transformer"] = PIXART_SIGMA
    with pytest.raises(ValueError, match="'sdxl': transformer is"):
        pipeline.program_config(config)


# -- XLA's count of a plain forward at a small size ---------------------------

def _layer_norm(x, eps=1e-6):
    m = x.mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(((x - m) ** 2).mean(-1, keepdims=True) + eps)


def _attention(x, ctx, w, heads, mask=None):
    q, k, v = (a @ w[n]["kernel"] + w[n]["bias"] for a, n in
               ((x, "to_q"), (ctx, "to_k"), (ctx, "to_v")))
    split = lambda a: a.reshape(a.shape[0], heads, -1).transpose(1, 0, 2)  # noqa: E731
    s = jnp.einsum("hqd,hkd->hqk", split(q), split(k)) / math.sqrt(q.shape[-1] // heads)
    if mask is not None:
        s = jnp.where(mask[None, None, :], s, -1e9)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), split(v))
    return o.transpose(1, 0, 2).reshape(x.shape[0], -1) @ w["to_out"]["kernel"] \
        + w["to_out"]["bias"]


def _dit_forward(w, x, t, caption, mask, cfg):
    """One row of a PixArt-style denoiser in plain ``jax.numpy``."""
    c, p = cfg.num_heads * cfg.head_dim, cfg.patch_size
    h = jax.lax.conv_general_dilated(x[None], w["patch_embed"]["kernel"], (p, p), "VALID",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h = h.reshape(-1, c) + w["patch_embed"]["bias"]
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(128) / 128)
    e = jnp.concatenate([jnp.cos(t * freqs), jnp.sin(t * freqs)])[None]
    lin = lambda a, d: a @ d["kernel"] + d["bias"]                         # noqa: E731
    temb = lin(jax.nn.silu(lin(e, w["time_embed"]["linear_1"])), w["time_embed"]["linear_2"])
    mod = lin(jax.nn.silu(temb), w["t_block"]).reshape(6, c)
    cap = lin(jax.nn.gelu(lin(caption, w["caption_proj"]["linear_1"]), approximate=True),
              w["caption_proj"]["linear_2"])
    for b in w["blocks"]:
        sh1, sc1, g1, sh2, sc2, g2 = b["scale_shift_table"] + mod
        n = _layer_norm(h) * (1 + sc1) + sh1
        h = h + g1 * _attention(n, n, b["self_attn"], cfg.num_heads)
        h = h + _attention(_layer_norm(h), cap, b["cross_attn"], cfg.num_heads, mask)
        n = _layer_norm(h) * (1 + sc2) + sh2
        h = h + g2 * lin(jax.nn.gelu(lin(n, b["ff_in"]), approximate=True), b["ff_out"])
    shift, scale = w["final"]["scale_shift_table"] + temb
    return lin(_layer_norm(h) * (1 + scale) + shift, w["final"]["proj_out"])


SMALL_DIT = DiT(sample_size=32, num_layers=2, num_heads=4, head_dim=64,
                context_dim=256, caption_channels=128, context_len=64,
                kernel_dtype="float32")


def test_transformer_count_against_xla_at_a_small_size():
    cfg = SMALL_DIT
    w = jax.eval_shape(lambda: init_transformer(None, cfg))
    args = (w, jax.ShapeDtypeStruct((32, 32, 4), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((64,), jnp.bool_))
    xla = jax.jit(lambda *a: _dit_forward(*a, cfg)).lower(*args).cost_analysis()["flops"]
    tc = dict(PIXART_SIGMA, sample_size=32, num_layers=2, num_attention_heads=4,
              attention_head_dim=64, cross_attention_dim=256, caption_channels=128,
              context_len=64)
    assert 0.98 < flops.transformer_forward_flops(tc) / xla <= 1.0


def _t5_forward(w, ids, buckets, t):
    """T5's encoder in plain ``jax.numpy``: RMS norms, relative position
    bias from buckets, gated-GELU feed-forward, layers stacked."""
    rms = lambda x, s: x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6) * s  # noqa: E731
    h = w["token_embed"][ids]
    bias = w["relative_attention_bias"][buckets].transpose(2, 0, 1)
    heads = t.num_heads
    L = w["layers"]

    def layer(h, i):
        x = rms(h, L["ln1"]["scale"][i])
        split = lambda a: a.reshape(a.shape[0], heads, -1).transpose(1, 0, 2)  # noqa: E731
        q, k, v = (split(x @ L[n]["kernel"][i]) for n in ("q", "k", "v"))
        p = jax.nn.softmax(jnp.einsum("hqd,hkd->hqk", q, k) + bias, -1)
        o = jnp.einsum("hqk,hkd->hqd", p, v).transpose(1, 0, 2).reshape(h.shape[0], -1)
        h = h + o @ L["out"]["kernel"][i]
        x = rms(h, L["ln2"]["scale"][i])
        g = jax.nn.gelu(x @ L["wi_0"]["kernel"][i], approximate=True) * (x @ L["wi_1"]["kernel"][i])
        return h + g @ L["wo"]["kernel"][i]

    for i in range(t.num_layers):
        h = layer(h, i)
    return rms(h, w["final_ln"]["scale"])


def test_t5_count_against_xla_at_a_small_size():
    t = With(dataclasses.replace(T5._base, hidden_dim=256, num_layers=2, num_heads=4,
                                 max_length=64, vocab_size=512, kernel_dtype="float32"),
             intermediate_size=640, relative_attention_num_buckets=32,
             relative_attention_max_distance=128)
    w = jax.eval_shape(lambda: init_t5(None, t))
    xla = jax.jit(lambda *a: _t5_forward(*a, t)).lower(
        w, jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64, 64), jnp.int32)).cost_analysis()["flops"]
    tc = dict(T5_XXL, hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
              attention_inner_dim=256, max_position_embeddings=64, intermediate_size=640)
    assert 0.98 < flops.text_encoder_flops(tc) / xla <= 1.0


# -- scopes -------------------------------------------------------------------

@pytest.mark.parametrize("scope,part", [
    ("dit/block3/self_attn/block6/qkv", "self_attn"),
    ("dit/block3/self_attn/block6/core", "self_attn"),
    ("dit/block3/cross_attn/block7/core", "cross_attn"),
    ("dit/block27/cross_attn/block55/out", "cross_attn"),
    ("dit/block3/ff", "ff"), ("dit/block3/modulate", "embed_mod"),
    ("dit/patch_embed", "embed_mod"), ("dit/time_embed", "embed_mod"),
    ("dit/caption_proj", "embed_mod"), ("dit/final", "embed_mod"),
    ("dit/block3", "embed_mod"), ("dit", "embed_mod"),
    ("unet/time_embed", "resblock"), ("sampler/cfg", "outside_unet")])
def test_transformer_scopes_fall_in_their_parts(scope, part):
    assert scopes.part_of(scope) == part and part in scopes.PARTS


def test_a_transformer_site_name_is_read_from_its_core_scope():
    from benchmarks.lib import self_sites

    m = self_sites._CORE.search("dit/block3/self_attn/block6/core")
    assert m and m.group(1) == "block6" in flops.self_site_names(PIXART_SIGMA)


# -- the fill -----------------------------------------------------------------

def test_the_stand_in_is_built_and_filled_at_a_small_size(standin, monkeypatch):
    """A toy of the stand-in's shapes through ``pipeline.build``: the
    denoiser's tree is handed over as ``unet_params``, the q/k gain reaches
    its ``to_q`` / ``to_k``, the tables and the relative bias are drawn by
    their rules, and the tower's stacked leaves keep their types."""
    from p2p_tpu.engine import sampler

    small = dataclasses.replace(SMALL_DIT, sample_size=8, num_layers=2, num_heads=2,
                                head_dim=8, context_dim=16, caption_channels=24,
                                context_len=6, kernel_dtype="bfloat16")
    tower = With(dataclasses.replace(T5._base, hidden_dim=24, num_layers=2, num_heads=2,
                                     max_length=6, vocab_size=64),
                 intermediate_size=40, relative_attention_num_buckets=8,
                 relative_attention_max_distance=16)
    pc = dataclasses.replace(STANDIN, unet=small, text=tower, vae=presets.TINY_VAE)
    monkeypatch.setitem(presets.PRESET_CONFIGS, "pixart_sigma", pc)
    monkeypatch.setattr(sampler, "Pipeline", lambda **kw: SimpleNamespace(**kw))
    config = dict(standin, **{k: v for k, v in pipeline._sizes_of_program(pc).items()})
    pipe, made = pipeline.build(config, SEED)
    assert pipe.unet_params is made["unet"] and pipe.text_params is made["text"]
    assert jax.tree.structure(made) == jax.tree.structure(pipeline.weight_shapes(pc))
    blk = made["unet"]["blocks"][1]
    bound = 1 / math.sqrt(16)
    for name in ("to_q", "to_k"):
        k = np.abs(np.asarray(blk["self_attn"][name]["kernel"], np.float32))
        assert blk["self_attn"][name]["kernel"].dtype == jnp.bfloat16
        assert bound < k.max() <= 3.0 * bound
    assert np.abs(np.asarray(blk["self_attn"]["to_v"]["kernel"], np.float32)).max() <= bound
    table = np.asarray(blk["scale_shift_table"])
    assert table.shape == (6, 16) and 0.1 < table.std() < 0.5      # 1/sqrt(16) = 0.25
    bias = np.asarray(made["text"]["relative_attention_bias"])
    assert bias.shape == (8, 2) and bias.std() > 0.2
    assert made["text"]["layers"]["wi_0"]["kernel"].shape == (2, 24, 40)
    assert made["text"]["layers"]["wi_0"]["kernel"].dtype == jnp.bfloat16


def _plan_digest(stacks, order, parts) -> str:
    return hashlib.sha256(repr((stacks, order, parts)).encode()).hexdigest()


#: sha256 of ``repr((stacks, order, parts))`` of the three cells' trees as
#: the parent's ``lib/weights.py`` planned them (``_stacks`` of the leaves'
#: rows, ``_parts`` under 6 GiB a call), before the fill was bounded by the
#: chip: the same plan is the same jitted fill, so the same bits.
PLANS_OF_THE_PARENT = {
    "sd14": ("08727cb09ec770f766b338bba3c0af5e9d850625971b3d3239c188c013ddac81", 1),
    "sd21": ("3ae8109e0d34077dc23a113da64a3c5a5f197889c93cfa39d29ba69f23e00a20", 1),
    "sdxl": ("9c1c784f30dfc75b088543426f9967a999a568580c98ac7dbae4a1c4c7f271cb", 3),
}


@pytest.mark.parametrize("preset", sorted(PLANS_OF_THE_PARENT))
def test_the_three_cells_trees_are_planned_as_the_parent_planned_them(preset):
    """On a 16 GiB chip the cells' trees keep the parent's plan, stack for
    stack and call for call, and none of their stacks reaches the slicing
    cap: the largest draw is one token table, 253 MB."""
    shapes = pipeline.weight_shapes(presets.PRESET_CONFIGS[preset])
    stacks, order, parts = weights.plan(shapes, 3.0, 16 * GIB)
    assert (_plan_digest(stacks, order, parts), len(parts)) == PLANS_OF_THE_PARENT[preset]
    assert max(weights._draw_bytes(s[0], s[-1]) for s in stacks) < weights.SLICE_BYTES / 4
    assert all(weights._slices(s) is None for s in stacks)


def test_a_tree_of_eleven_gigabytes_plans_no_call_past_the_chip(standin):
    """PixArt-Sigma's denoiser, T5-XXL with its layers stacked and SDXL's
    autoencoder, kernels in bfloat16: 11.2 GB. Every stack's slices are at
    most the cap, and every call's draws fit beside the whole tree on a
    16 GiB chip."""
    shapes = pipeline.weight_shapes(STANDIN)
    tree = weights.tree_bytes(shapes)
    assert 11.0e9 < tree < 11.4e9
    stacks, order, parts = weights.plan(shapes, 3.0, 16 * GIB)
    budget = weights.call_budget(tree, 16 * GIB)
    assert budget < weights.FILL_BYTES and tree + budget + weights.HEADROOM_BYTES <= 16 * GIB
    sliced = [s for s in stacks if weights._slices(s)]
    assert len(sliced) == 7                        # q, k, v, out, wi_0, wi_1, wo
    for s in sliced:
        axis, sizes = weights._slices(s)
        assert axis == 1 and sum(sizes) == s[0][0] == 24
        rest = math.prod(s[0][1:])
        assert all(4 * n * rest <= weights.SLICE_BYTES for n in sizes)
    for first, end in parts:
        assert sum(weights._draw_bytes(s[0], s[-1]) for s in stacks[first:end]) <= budget
    assert [i for first, end in parts for i in range(first, end)] == list(range(len(stacks)))
    with pytest.raises(ValueError, match="leaves"):
        weights.call_budget(tree, 12 * GIB)


def test_an_over_cap_stack_is_drawn_in_slices(monkeypatch):
    """A leaf over the cap is drawn in slices of its leading axis, each from
    a key of its own, and joined in its type; every other leaf is the same
    bits as when nothing is sliced."""
    shapes = {"big": {"kernel": jax.ShapeDtypeStruct((3, 20, 44), jnp.bfloat16)},
              "small": {"kernel": jax.ShapeDtypeStruct((20, 44), jnp.float32),
                        "bias": jax.ShapeDtypeStruct((44,), jnp.float32)}}
    whole = weights.make_weights(SEED, shapes, 3.0)
    drawn = []
    uniform = jax.random.uniform

    def spy(key, shape, *a, **k):
        drawn.append(tuple(shape))
        return uniform(key, shape, *a, **k)

    monkeypatch.setattr(weights, "SLICE_BYTES", 2 * 20 * 44 * 4)
    monkeypatch.setattr(jax.random, "uniform", spy)
    weights._fill.clear_cache()        # the cap is read where the fill is traced
    sliced = weights.make_weights(SEED, shapes, 3.0)
    weights._fill.clear_cache()
    assert sorted(s for s in drawn if len(s) == 4) == [(1, 1, 20, 44), (1, 2, 20, 44)]
    big, was = np.asarray(sliced["big"]["kernel"], np.float32), np.asarray(
        whole["big"]["kernel"], np.float32)
    assert sliced["big"]["kernel"].dtype == jnp.bfloat16 and big.shape == (3, 20, 44)
    assert not np.array_equal(big, was) and np.abs(big).max() <= 1 / math.sqrt(60) * 1.001
    assert not np.array_equal(big[2], big[0])
    for name in ("kernel", "bias"):
        np.testing.assert_array_equal(sliced["small"][name], whole["small"][name])


# -- the control --------------------------------------------------------------

def test_the_control_narrows_only_floating_leaves():
    """A conditioning of context, pooled text and an integer caption mask:
    the floats go to bfloat16, the mask keeps its type and values."""
    cond = {"context": jnp.ones((2, 6, 16)), "pooled": jnp.ones((2, 16)),
            "mask": jnp.array([[1, 1, 1, 0, 0, 0]] * 2, jnp.int32)}
    got = controls.narrowed(cond)
    assert got["context"].dtype == got["pooled"].dtype == jnp.bfloat16
    assert got["mask"].dtype == jnp.int32
    np.testing.assert_array_equal(got["mask"], cond["mask"])
    seen = {}

    def sweep(pipe, context, latents, *a, **k):
        seen.update(context=context, latents=latents)
        return "images", jnp.zeros((1,), jnp.bfloat16)

    import importlib

    from p2p_tpu import parallel

    sweep_mod = importlib.import_module("p2p_tpu.parallel.sweep")
    original = sweep_mod.sweep
    sweep_mod.sweep = parallel.sweep = sweep
    try:
        with controls.bfloat16():
            _, final = sweep_mod.sweep(None, cond, jnp.ones((1, 4)))
    finally:
        sweep_mod.sweep = parallel.sweep = original
    assert seen["context"]["mask"].dtype == jnp.int32
    assert seen["latents"].dtype == jnp.bfloat16 and final.dtype == jnp.float32


# -- where the loop comes from ------------------------------------------------

def _toy(x, w):
    def body(c, t):
        y = jnp.tanh(c @ w) + t
        y = jax.lax.cond(t > 2.0, lambda v: v * 2.0, lambda v: v - 1.0, y)
        return y, None

    c, _ = jax.lax.scan(body, x * 3.0, jnp.arange(5.0))
    return jnp.sin(c).sum()


def test_loop_by_text_is_loop_by_nesting_on_a_cpu_program(tmp_path):
    """The toy's CPU trace names each executed instruction and nests the
    body's under the ``while``'s event; the compiled text puts the same
    instructions in the loop, the conditional's branch among them."""
    x, w = jnp.ones((32, 32)), jnp.full((32, 32), 0.01)
    fn = jax.jit(_toy)
    text = fn.lower(x, w).compile().as_text()
    fn(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    fn(x, w).block_until_ready()
    jax.profiler.stop_trace()
    opcodes = {}
    for line in text.splitlines():
        m = T._TEXT_INSTR.match(line)
        if m:
            opcodes[m.group(1)] = m.group(2)
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0])
    lines = [[e for e in line.events if e.name in opcodes]
             for plane in pd.planes for line in plane.lines]
    events = max(lines, key=lambda es: sum(opcodes[e.name] == "while" for e in es))
    ops = sorted((T.Op(e.name, e.start_ns, e.duration_ns, opcodes[e.name], "jit__toy")
                  for e in events), key=lambda o: (o.start, -o.dur))
    T.mark_leaves(ops)
    by_nesting = {o.name: o.loop for o in ops}
    loops = T.program_loops(text)
    assert by_nesting == {o.name: o.name in loops.names for o in ops}
    assert sum(by_nesting.values()) >= 4 and not all(by_nesting.values())
    assert any(opcodes[n] == "conditional" and inside for n, inside in by_nesting.items())
    (body,) = loops.bodies.values()
    counts = {n: sum(o.name == n for o in ops) for n in body if n in by_nesting}
    assert set(counts.values()) == {5}                   # once an iteration
    trace = T.Trace(devices={"/device:CPU:0": ops})
    lo, hi = ops[0].start, max(o.end for o in ops)
    assert T.incomplete(trace, lo, hi, {"jit__toy": loops}, 5) is None
    assert "ran 10 steps" in T.incomplete(trace, lo, hi, {"jit__toy": loops}, 10)


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MODULE = "jit__text2image_jit"
#: The readers of the loop: what a lost ``while`` left null or 0.0.
LOOP_READERS = ("sampler.step_ms", "model.resblock_ms_per_step",
                "model.self_attn_ms_per_step", "model.cross_attn_ms_per_step",
                "model.ff_ms_per_step", "sampler.outside_unet_ms_per_step",
                "kernels.self_attn_roofline")
MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def _recorded(drop_while=False, lose_tail=0.0):
    with gzip.open(os.path.join(DATA, "trace_sd14_scoped_2steps.json.gz"), "rt") as f:
        d = json.load(f)
    ops = d["devices"]["/device:TPU:0"]
    if drop_while:
        ops = [o for o in ops if o[3] != "while"]
    if lose_tail:
        cut = min(o[1] for o in ops) + (1 - lose_tail) * (max(o[1] + o[2] for o in ops)
                                                          - min(o[1] for o in ops))
        ops = [o for o in ops if o[1] + o[2] <= cut]
    d["devices"]["/device:TPU:0"] = ops
    with gzip.open(os.path.join(DATA, "trace_sd14_scoped_index.json.gz"), "rt") as f:
        indexes = {m: tuple(pair) for m, pair in json.load(f).items()}
    return T.Trace.from_dict(d), indexes


def _fake_run(trace, indexes, **more):
    lo, hi = T.window_of(trace)
    records = [{"t_start": 10.0, "t_end": 12.5, "images": 2}]
    fields = dict(
        trace_data=trace, trace_window=(lo, hi), scope_indexes=indexes,
        traced=(10.0, 12.5), on_chip=True, device={"kind": "TPU v5 lite"},
        spans=SimpleNamespace(rows=[("call", 10.0, 12.5, 0)]), trace_incomplete=None,
        program_texts={}, self_site_hows={}, config=SD14,
        t_process=0.0, t_setup_done=9.0, traced_records=lambda: records, done=records,
        work_of=lambda recs: {"steps": 2, "images": 2, "prompts": 4,
                              "unet_rows_full": 8, "unet_rows_cached": 0})
    fields.update(more)
    return SimpleNamespace(**fields)


def _the_loop_as_text(trace) -> str:
    """A program text whose ``while`` body holds the instructions the
    recorded trace nests under its ``while`` event."""
    names = sorted({o.name for ops in trace.devices.values() for o in ops if o.loop})
    return "\n".join(
        ["%body.1 (p.1: (s32[])) -> (s32[]) {"]
        + [f"  %{n} = f32[4]{{0}} fusion(f32[4]{{0}} %p.1), kind=kLoop" for n in names]
        + ["}", "%cond.1 (p.2: (s32[])) -> pred[] {",
           "  ROOT %lt.1 = pred[] compare(s32[] %p.2, s32[] %c.1), direction=LT", "}",
           "ENTRY %main.1 () -> (s32[]) {",
           "  ROOT %while.3 = (s32[]) while((s32[]) %t.1), condition=%cond.1, body=%body.1",
           "}"])


def _per_layer(run):
    entries = [m for m in MANIFEST["per_layer"] if m["source"] == "device_trace"]
    harness.judge_trace(run)
    return harness.read_metrics(run, entries)


def test_the_recorded_trace_is_whole_and_reads_as_before():
    trace, indexes = _recorded()
    run = _fake_run(trace, indexes)
    got = _per_layer(run)
    assert run.trace_incomplete is None
    assert 46.0 < got["sampler.step_ms"]["value"] < 48.0
    assert all(got[m]["value"] > 0 for m in LOOP_READERS)
    assert scopes.load(run).loop_ms_per_step("embed_mod") == 0.0


def test_a_lost_while_is_read_the_same_from_the_program_text():
    trace, indexes = _recorded()
    want = _per_layer(_fake_run(trace, indexes))
    text = _the_loop_as_text(trace)
    lost, indexes = _recorded(drop_while=True)
    run = _fake_run(lost, indexes, program_texts={MODULE: text})
    got = _per_layer(run)
    assert run.trace_incomplete is None
    assert set(got) == set(want)
    for name in want:
        assert got[name]["value"] == pytest.approx(want[name]["value"], rel=1e-12), name


def test_a_lost_while_without_program_text_is_refused_by_name():
    lost, indexes = _recorded(drop_while=True)
    run = _fake_run(lost, indexes)
    got = _per_layer(run)
    assert MODULE in run.trace_incomplete and "no operation of them" in run.trace_incomplete
    assert not set(LOOP_READERS) & set(got)
    assert all(v["value"] not in (None, 0.0) for v in got.values())


def test_a_lost_tail_is_refused_with_the_program_text_and_without():
    trace, _ = _recorded()
    text = _the_loop_as_text(trace)
    for texts in ({MODULE: text}, {}):
        lost, indexes = _recorded(drop_while=True, lose_tail=0.3)
        run = _fake_run(lost, indexes, program_texts=texts)
        got = _per_layer(run)
        assert run.trace_incomplete and MODULE in run.trace_incomplete
        assert not set(LOOP_READERS) & set(got)


# -- a trace that is not whole is traced again, once ---------------------------

@pytest.mark.parametrize("lost", (1, 2), ids=("once", "twice"))
def test_an_incomplete_trace_is_retaken_once(monkeypatch, capsys, lost):
    reads, again = [], []

    def read(run):
        reads.append(1)
        run.trace_incomplete = "lost" if len(reads) <= lost else None

    monkeypatch.setattr(harness, "read_trace", read)
    run = SimpleNamespace(trace_incomplete=None, driver=SimpleNamespace(
        trace_again=lambda run, state: again.append(state)))
    harness.read_traced_calls(run, "state")
    assert (len(reads), again) == (2, ["state"])
    err = capsys.readouterr().err
    assert "trace not read (lost): one more call traced" in err
    assert ("trace incomplete: lost" in err) == (lost == 2)
    assert (run.trace_incomplete is None) == (lost == 1)


def test_the_retaken_call_is_the_traced_one_and_not_the_windows(monkeypatch):
    """``window.trace_one_more`` traces one more request after the window:
    its record is the traced one, and the window's records, rate and check
    do not see it."""
    from benchmarks.lib import timing, window

    run = harness.Run(manifest={}, cell={}, config={}, traffic={}, seed=SEED,
                      seconds=1.0, trace=True, t_process=0.0, on_chip=False,
                      clock=SimpleNamespace())
    monkeypatch.setattr(harness.Run, "start_trace",
                        lambda self: setattr(self, "_trace_t0", time.monotonic()))
    monkeypatch.setattr(harness.Run, "stop_trace", lambda self: setattr(
        self, "traced", (self._trace_t0, time.monotonic())))
    run.spans = timing.Spans()
    run.records = [{"index": 0, "images": 2, "t_start": -2.0, "t_end": -1.0}]
    window.trace_one_more(run, lambda i: {"index": i, "images": 2})
    assert [r["index"] for r in run.traced_records()] == [1]
    assert run.done == run.records and len(run.records) == 1
