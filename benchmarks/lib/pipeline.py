"""The system under test, built from a configuration file: the program's
``Pipeline`` for the preset the file names, checked size by size against the
file, holding the benchmark's weights.

The file's schema is ``lib/flops.py``'s, and a preset may be any member of
the ``UNet2DConditionModel`` family, or a transformer denoiser over patch
tokens with cross-attention, as the program presents it:
``_sizes_of_program`` states, attribute by attribute, what the harness reads
of one, and ``weight_shapes`` which initialiser builds its tree."""

from __future__ import annotations

import jax


def _towers(pc) -> tuple:
    """The preset's text towers in order: ``pc.text`` is one tower's
    configuration or a sequence of them."""
    return tuple(pc.text) if isinstance(pc.text, (list, tuple)) else (pc.text,)


def _by_tower(pc, f):
    """``f`` of the one tower, or the list of ``f`` of each of a sequence."""
    each = [f(t) for t in _towers(pc)]
    return each if isinstance(pc.text, (list, tuple)) else each[0]


def _given(cfg, name: str) -> bool:
    """Whether a configuration has attribute ``name``, not None."""
    return getattr(cfg, name, None) is not None


def _kind(cfg) -> str:
    """The denoiser's kind: ``"unet"`` unless its configuration says
    otherwise by a ``kind`` attribute."""
    return getattr(cfg, "kind", "unet")


def _sizes_of_tower(t) -> dict:
    sizes = {
        "arch": t.arch, "vocab_size": t.vocab_size,
        "hidden_size": t.hidden_dim, "num_hidden_layers": t.num_layers,
        "num_attention_heads": t.num_heads,
        "attention_inner_dim": t.inner_dim,
        "max_position_embeddings": t.max_length, "ff_mult": t.ff_mult,
        "hidden_act": t.activation, "causal": t.causal,
        "qkv_bias": t.attn_qkv_bias,
    }
    if _given(t, "intermediate_size"):
        del sizes["ff_mult"]
        sizes["intermediate_size"] = t.intermediate_size
    for name in ("relative_attention_num_buckets",
                 "relative_attention_max_distance", "projection_dim"):
        if _given(t, name):
            sizes[name] = getattr(t, name)
    return sizes


def _sizes_of_unet(u) -> dict:
    depth = u.transformer_depth
    unet = {
        "sample_size": u.sample_size, "in_channels": u.in_channels,
        "out_channels": u.out_channels,
        "block_out_channels": list(u.block_channels),
        "attention_levels": list(u.attn_levels),
        "layers_per_block": u.layers_per_block,
        "transformer_depth": depth if isinstance(depth, int) else list(depth),
        "num_attention_heads": None if u.head_dim else u.num_heads,
        "attention_head_size": u.head_dim,
        "cross_attention_dim": u.context_dim, "context_len": u.context_len,
        "norm_num_groups": u.groups, "ff_mult": u.ff_mult,
    }
    if _given(u, "addition_embed_in"):
        unet["addition_embed_in"] = u.addition_embed_in
    return unet


def _sizes_of_transformer(d) -> dict:
    return {
        "sample_size": d.sample_size, "patch_size": d.patch_size,
        "in_channels": d.in_channels, "out_channels": d.out_channels,
        "num_layers": d.num_layers, "num_attention_heads": d.num_heads,
        "attention_head_dim": d.head_dim,
        "cross_attention_dim": d.context_dim,
        "caption_channels": d.caption_channels, "context_len": d.context_len,
        "norm_type": d.norm_type, "activation_fn": d.activation,
        "ff_mult": d.ff_mult, "attention_bias": d.attention_bias,
        "use_additional_conditions": d.use_additional_conditions,
        "interpolation_scale": d.interpolation_scale,
    }


def _sizes_of_program(pc) -> dict:
    """The program's preset in the configuration file's own keys.

    The contract a preset is held to: a key stands for an attribute the
    preset has, and none appears for one it lacks, so a key on one side only
    is a refusal, by the name of its block.

    - The denoiser is ``PipelineConfig.unet`` whatever it is; its kind is
      ``getattr(pc.unet, "kind", "unet")``, and the file holds a ``unet``
      block for a U-Net and a ``transformer`` block for a transformer, never
      both. Its tree is built by ``p2p_tpu.models.init_unet(key, cfg)`` or
      ``init_transformer(key, cfg)`` and handed over as
      ``Pipeline(unet_params=...)`` for either kind: the field keeps that
      name, since a new one would be an edit of this file.
    - ``unet.transformer_depth``: ``UNetConfig.transformer_depth`` as it is
      where it is an int; a list where it is a sequence, one int a level, 0
      where the level has no transformer (the mid block takes the last).
    - ``unet.addition_embed_in``: only where the U-Net's configuration has an
      attribute of that name that is not None.
    - ``transformer`` (diffusers' ``PixArtTransformer2DModel`` names, from
      attributes of the same meaning): ``sample_size`` (the latent's side),
      ``patch_size``, ``in_channels``, ``out_channels`` (twice the latent's
      channels where the variance is learned), ``num_layers``,
      ``num_attention_heads`` (``num_heads``), ``attention_head_dim``
      (``head_dim``), ``cross_attention_dim`` (``context_dim``: the width the
      caption is projected to), ``caption_channels`` (the text tower's
      width), ``context_len`` (caption tokens, all of them attended to under
      a key mask), ``norm_type`` (``"ada_norm_single"``: one timestep MLP to
      six times the width and a learned ``(6, width)`` table a block),
      ``activation_fn`` (``activation``; ``"gelu-approximate"``),
      ``ff_mult``, ``attention_bias``, ``use_additional_conditions``,
      ``interpolation_scale`` (of the 2-D sin-cos positions). Its weight
      tree names its attention projections ``to_q``, ``to_k``, ``to_v`` and
      ``to_out`` (``lib/weights.py`` gives the first two the logit gain) and
      its modulation tables by a leaf name that ends in ``table``.
    - ``text_encoder``: one tower's dict where ``PipelineConfig.text`` is one
      tower's configuration; a list of such dicts, in order, where it is a
      sequence of them. A tower's dict has ``projection_dim``,
      ``relative_attention_num_buckets`` and
      ``relative_attention_max_distance`` only where the tower's
      configuration has an attribute of that name that is not None, and
      ``intermediate_size`` in place of ``ff_mult`` where it has that one
      (a feed-forward not a multiple of the width, as T5's 10240 of 4096;
      ``hidden_act`` then names a gated one ``gated-<act>``). For an encoder
      without positions, as T5 (``arch`` ``"t5"``, relative position
      buckets instead), ``max_position_embeddings`` states the tokenizer's
      length, the caption's tokens (300 for PixArt-Sigma).
    """
    v, s = pc.vae, pc.scheduler
    if _kind(pc.unet) == "transformer":
        denoiser = {"transformer": _sizes_of_transformer(pc.unet)}
    else:
        denoiser = {"unet": _sizes_of_unet(pc.unet)}
    return {
        "image_size": pc.image_size,
        "guidance_scale": pc.guidance_scale,
        "num_inference_steps": pc.num_steps,
        **denoiser,
        "text_encoder": _by_tower(pc, _sizes_of_tower),
        "vae": {
            "kind": v.kind, "in_channels": v.in_channels,
            "latent_channels": v.latent_channels,
            "base_channels": v.base_channels,
            "channel_mults": list(v.channel_mults),
            "layers_per_block": v.layers_per_block, "norm_num_groups": v.groups,
            "scaling_factor": v.scaling_factor,
            "num_codebook": v.num_codebook if v.kind == "vq" else None,
        },
        "scheduler": {
            "kind": s.kind, "num_train_timesteps": s.num_train_timesteps,
            "beta_start": s.beta_start, "beta_end": s.beta_end,
            "beta_schedule": s.beta_schedule,
            "set_alpha_to_one": s.set_alpha_to_one,
            "steps_offset": s.ddim_steps_offset,
        },
    }


def program_config(config: dict):
    """The program's preset, refused where it is not what the file states."""
    from p2p_tpu.models.config import PRESET_CONFIGS

    pc = PRESET_CONFIGS[config["preset"]]
    sizes = _sizes_of_program(pc)
    for key in list(sizes) + [k for k in _DENOISERS if k not in sizes]:
        have, want = config.get(key, _ABSENT), sizes.get(key, _ABSENT)
        if have != want:
            raise ValueError(
                f"configuration {config['name']!r}: {key} is {have!r} "
                f"in the file and {want!r} in the program's preset")
    return pc


#: The denoiser blocks a file may hold, one of them.
_DENOISERS = ("unet", "transformer")


class _Absent:
    """A key on the other side only."""

    def __repr__(self):
        return "absent"


_ABSENT = _Absent()


def weight_shapes(pc):
    """The shapes and types of the program's own initialisers. ``"unet"`` is
    the denoiser's tree, of either kind; ``"text"`` is the tower's tree, or a
    list of trees in the towers' order."""
    from p2p_tpu import models
    from p2p_tpu.models import init_text_encoder
    from p2p_tpu.models import vae as vae_mod

    key = jax.random.PRNGKey(0)
    init = getattr(models, f"init_{_kind(pc.unet)}")
    return {
        "unet": jax.eval_shape(lambda: init(key, pc.unet)),
        "text": _by_tower(pc, lambda t: jax.eval_shape(
            lambda: init_text_encoder(key, t))),
        "vae": jax.eval_shape(lambda: vae_mod.init_vae(key, pc.vae)),
    }


def build(config: dict, seed: int):
    """``(pipeline, weights)``: the weights tree is shared, not copied.
    ``Pipeline.text_params`` is handed what ``weights["text"]`` is, a tree or
    a list of trees. Every tower reads the ids of one tokenizer, the first
    tower's."""
    from p2p_tpu.engine.sampler import Pipeline
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    from .weights import make_weights

    pc = program_config(config)
    first, *others = _towers(pc)
    for t in others:
        if (t.vocab_size, t.max_length) != (first.vocab_size, first.max_length):
            raise ValueError(
                f"configuration {config['name']!r}: the harness has one "
                f"tokenizer, and a tower of {t.vocab_size} ids and "
                f"{t.max_length} positions stands beside one of "
                f"{first.vocab_size} and {first.max_length}")
    weights = make_weights(seed, weight_shapes(pc),
                           config["assumed"]["attention_logit_gain"])
    tok = HashWordTokenizer(vocab_size=first.vocab_size,
                            model_max_length=first.max_length)
    pipe = Pipeline(config=pc, unet_params=weights["unet"],
                    text_params=weights["text"], vae_params=weights["vae"],
                    tokenizer=tok)
    return pipe, weights
