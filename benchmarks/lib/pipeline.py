"""The system under test, built from a configuration file: the program's
``Pipeline`` for the preset the file names, checked size by size against the
file, holding the benchmark's weights."""

from __future__ import annotations

import jax


def _sizes_of_program(pc) -> dict:
    """The program's preset in the configuration file's own keys."""
    u, t, v, s = pc.unet, pc.text, pc.vae, pc.scheduler
    return {
        "image_size": pc.image_size,
        "guidance_scale": pc.guidance_scale,
        "num_inference_steps": pc.num_steps,
        "unet": {
            "sample_size": u.sample_size, "in_channels": u.in_channels,
            "out_channels": u.out_channels,
            "block_out_channels": list(u.block_channels),
            "attention_levels": list(u.attn_levels),
            "layers_per_block": u.layers_per_block,
            "transformer_depth": u.transformer_depth,
            "num_attention_heads": None if u.head_dim else u.num_heads,
            "attention_head_size": u.head_dim,
            "cross_attention_dim": u.context_dim, "context_len": u.context_len,
            "norm_num_groups": u.groups, "ff_mult": u.ff_mult,
        },
        "text_encoder": {
            "arch": t.arch, "vocab_size": t.vocab_size,
            "hidden_size": t.hidden_dim, "num_hidden_layers": t.num_layers,
            "num_attention_heads": t.num_heads,
            "attention_inner_dim": t.inner_dim,
            "max_position_embeddings": t.max_length, "ff_mult": t.ff_mult,
            "hidden_act": t.activation, "causal": t.causal,
            "qkv_bias": t.attn_qkv_bias,
        },
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
    for key, want in _sizes_of_program(pc).items():
        if config[key] != want:
            raise ValueError(
                f"configuration {config['name']!r}: {key} is {config[key]!r} "
                f"in the file and {want!r} in the program's preset")
    return pc


def weight_shapes(pc):
    from p2p_tpu.models import init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod

    key = jax.random.PRNGKey(0)
    return {
        "unet": jax.eval_shape(lambda: init_unet(key, pc.unet)),
        "text": jax.eval_shape(lambda: init_text_encoder(key, pc.text)),
        "vae": jax.eval_shape(lambda: vae_mod.init_vae(key, pc.vae)),
    }


def build(config: dict, seed: int):
    """``(pipeline, weights)``: the weights tree is shared, not copied."""
    from p2p_tpu.engine.sampler import Pipeline
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    from .weights import make_weights

    pc = program_config(config)
    weights = make_weights(seed, weight_shapes(pc),
                           config["assumed"]["attention_logit_gain"])
    tok = HashWordTokenizer(vocab_size=pc.text.vocab_size,
                            model_max_length=pc.text.max_length)
    pipe = Pipeline(config=pc, unet_params=weights["unet"],
                    text_params=weights["text"], vae_params=weights["vae"],
                    tokenizer=tok)
    return pipe, weights
