"""Checkpoint I/O: load diffusers-format SD weights into our param trees.

The reference gets all weights via `StableDiffusionPipeline.from_pretrained`
(`/root/reference/main.py:29`, `/root/reference/null_text.py:28-31`). Here the
mapping diffusers-name → our-tree-path is explicit data (one table per
sub-model), applied in both directions:

- :func:`load_unet` / :func:`load_text_encoder` / :func:`load_vae` read a
  local checkpoint directory (torch ``.bin`` via ``torch.load`` on CPU, or
  ``.safetensors`` when the library is present) and return our pytrees.
- :func:`export_state_dict` produces a diffusers-named state dict from our
  tree — used by the round-trip tests, and the parity harness.

Weight-layout transforms: torch Linear stores (out, in) — ours is (in, out);
torch Conv stores (O, I, kH, kW) — ours is HWIO.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import numpy as np

from .config import TextEncoderConfig, UNetConfig, VAEConfig

# A mapping entry: (our_path, their_name, kind) where kind selects the
# layout transform: 'linear' | 'conv' | 'none'.
Entry = Tuple[Tuple[Any, ...], str, str]


def _t_linear(w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(w.T)


def _t_conv(w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.transpose(w, (2, 3, 1, 0)))


_FWD = {"linear": _t_linear, "conv": _t_conv, "none": lambda w: w}
# All transforms are involutions up to transposition back.
_INV = {"linear": _t_linear, "conv": lambda w: np.transpose(w, (3, 2, 0, 1)),
        "none": lambda w: w}


def _lin(our_prefix, their_prefix, bias=True) -> List[Entry]:
    out = [(our_prefix + ("kernel",), their_prefix + ".weight", "linear")]
    if bias:
        out.append((our_prefix + ("bias",), their_prefix + ".bias", "none"))
    return out


def _conv(our_prefix, their_prefix) -> List[Entry]:
    return [(our_prefix + ("kernel",), their_prefix + ".weight", "conv"),
            (our_prefix + ("bias",), their_prefix + ".bias", "none")]


def _norm(our_prefix, their_prefix) -> List[Entry]:
    return [(our_prefix + ("scale",), their_prefix + ".weight", "none"),
            (our_prefix + ("bias",), their_prefix + ".bias", "none")]


def _resnet(our, their, has_skip: bool, time: bool = True) -> List[Entry]:
    e = (_norm(our + ("norm1",), their + ".norm1")
         + _conv(our + ("conv1",), their + ".conv1")
         + _norm(our + ("norm2",), their + ".norm2")
         + _conv(our + ("conv2",), their + ".conv2"))
    if time:
        e += _lin(our + ("time_proj",), their + ".time_emb_proj")
    if has_skip:
        e += _conv(our + ("skip",), their + ".conv_shortcut")
    return e


def _attn(our, their) -> List[Entry]:
    return (_lin(our + ("to_q",), their + ".to_q", bias=False)
            + _lin(our + ("to_k",), their + ".to_k", bias=False)
            + _lin(our + ("to_v",), their + ".to_v", bias=False)
            + _lin(our + ("to_out",), their + ".to_out.0"))


def _tblock(our, their) -> List[Entry]:
    return (_norm(our + ("ln1",), their + ".norm1")
            + _attn(our + ("attn1",), their + ".attn1")
            + _norm(our + ("ln2",), their + ".norm2")
            + _attn(our + ("attn2",), their + ".attn2")
            + _norm(our + ("ln3",), their + ".norm3")
            + _lin(our + ("ff_in",), their + ".ff.net.0.proj")
            + _lin(our + ("ff_out",), their + ".ff.net.2"))


def _spatial_transformer(our, their, depth: int) -> List[Entry]:
    e = (_norm(our + ("norm",), their + ".norm")
         + _conv(our + ("proj_in",), their + ".proj_in"))
    for d in range(depth):
        e += _tblock(our + ("blocks", d), their + f".transformer_blocks.{d}")
    e += _conv(our + ("proj_out",), their + ".proj_out")
    return e


def unet_entries(cfg: UNetConfig) -> List[Entry]:
    e: List[Entry] = []
    e += _lin(("time_fc1",), "time_embedding.linear_1")
    e += _lin(("time_fc2",), "time_embedding.linear_2")
    e += _conv(("conv_in",), "conv_in")

    n = cfg.levels
    ch = list(cfg.block_channels)
    in_ch = ch[0]
    skip_chs = [ch[0]]
    for lvl in range(n):
        out_ch = ch[lvl]
        for j in range(cfg.layers_per_block):
            e += _resnet(("down", lvl, "resnets", j),
                         f"down_blocks.{lvl}.resnets.{j}", has_skip=in_ch != out_ch)
            if cfg.attn_levels[lvl]:
                e += _spatial_transformer(("down", lvl, "attns", j),
                                          f"down_blocks.{lvl}.attentions.{j}",
                                          cfg.transformer_depth)
            in_ch = out_ch
            skip_chs.append(out_ch)
        if lvl != n - 1:
            e += _conv(("down", lvl, "downsample"),
                       f"down_blocks.{lvl}.downsamplers.0.conv")
            skip_chs.append(out_ch)

    e += _resnet(("mid", "resnet1"), "mid_block.resnets.0", has_skip=False)
    e += _spatial_transformer(("mid", "attn"), "mid_block.attentions.0",
                              cfg.transformer_depth)
    e += _resnet(("mid", "resnet2"), "mid_block.resnets.1", has_skip=False)

    in_ch = ch[-1]
    for pos, lvl in enumerate(reversed(range(n))):
        out_ch = ch[lvl]
        for j in range(cfg.layers_per_block + 1):
            skip_ch = skip_chs.pop()
            e += _resnet(("up", pos, "resnets", j),
                         f"up_blocks.{pos}.resnets.{j}",
                         has_skip=(in_ch + skip_ch) != out_ch)
            if cfg.attn_levels[lvl]:
                e += _spatial_transformer(("up", pos, "attns", j),
                                          f"up_blocks.{pos}.attentions.{j}",
                                          cfg.transformer_depth)
            in_ch = out_ch
        if lvl != 0:
            e += _conv(("up", pos, "upsample"),
                       f"up_blocks.{pos}.upsamplers.0.conv")

    e += _norm(("norm_out",), "conv_norm_out")
    e += _conv(("conv_out",), "conv_out")
    return e


def text_encoder_entries(cfg: TextEncoderConfig) -> List[Entry]:
    e: List[Entry] = [
        (("token_embed",), "text_model.embeddings.token_embedding.weight", "none"),
        (("pos_embed",), "text_model.embeddings.position_embedding.weight", "none"),
    ]
    for i in range(cfg.num_layers):
        base = f"text_model.encoder.layers.{i}"
        e += _norm(("layers", i, "ln1"), base + ".layer_norm1")
        e += _lin(("layers", i, "q"), base + ".self_attn.q_proj")
        e += _lin(("layers", i, "k"), base + ".self_attn.k_proj")
        e += _lin(("layers", i, "v"), base + ".self_attn.v_proj")
        e += _lin(("layers", i, "out"), base + ".self_attn.out_proj")
        e += _norm(("layers", i, "ln2"), base + ".layer_norm2")
        e += _lin(("layers", i, "fc1"), base + ".mlp.fc1")
        e += _lin(("layers", i, "fc2"), base + ".mlp.fc2")
    e += _norm(("final_ln",), "text_model.final_layer_norm")
    return e


def ldm_text_encoder_entries(cfg: TextEncoderConfig) -> List[Entry]:
    """diffusers ``LDMBertModel`` names (the `model.bert` the reference's LDM
    path encodes with, `/root/reference/ptp_utils.py:113`): pre-norm encoder
    layers under ``model.layers.N``, learned position embeddings, final
    ``model.layer_norm``. The unused ``to_logits`` head is ignored on load."""
    e: List[Entry] = [
        (("token_embed",), "model.embed_tokens.weight", "none"),
        (("pos_embed",), "model.embed_positions.weight", "none"),
    ]
    for i in range(cfg.num_layers):
        base = f"model.layers.{i}"
        e += _norm(("layers", i, "ln1"), base + ".self_attn_layer_norm")
        e += _lin(("layers", i, "q"), base + ".self_attn.q_proj",
                  bias=cfg.attn_qkv_bias)
        e += _lin(("layers", i, "k"), base + ".self_attn.k_proj",
                  bias=cfg.attn_qkv_bias)
        e += _lin(("layers", i, "v"), base + ".self_attn.v_proj",
                  bias=cfg.attn_qkv_bias)
        e += _lin(("layers", i, "out"), base + ".self_attn.out_proj")
        e += _norm(("layers", i, "ln2"), base + ".final_layer_norm")
        e += _lin(("layers", i, "fc1"), base + ".fc1")
        e += _lin(("layers", i, "fc2"), base + ".fc2")
    e += _norm(("final_ln",), "model.layer_norm")
    return e


def _vae_attn(our, their) -> List[Entry]:
    return (_norm(our + ("norm",), their + ".group_norm")
            + _lin(our + ("q",), their + ".query")
            + _lin(our + ("k",), their + ".key")
            + _lin(our + ("v",), their + ".value")
            + _lin(our + ("out",), their + ".proj_attn"))


def vae_entries(cfg: VAEConfig) -> List[Entry]:
    e: List[Entry] = []
    chs = [cfg.base_channels * m for m in cfg.channel_mults]
    n = len(chs)

    e += _conv(("encoder", "conv_in"), "encoder.conv_in")
    in_ch = chs[0]
    for lvl in range(n):
        out_ch = chs[lvl]
        for j in range(cfg.layers_per_block):
            e += _resnet(("encoder", "down", lvl, "resnets", j),
                         f"encoder.down_blocks.{lvl}.resnets.{j}",
                         has_skip=in_ch != out_ch, time=False)
            in_ch = out_ch
        if lvl != n - 1:
            e += _conv(("encoder", "down", lvl, "downsample"),
                       f"encoder.down_blocks.{lvl}.downsamplers.0.conv")
    e += _resnet(("encoder", "mid", "resnet1"), "encoder.mid_block.resnets.0",
                 has_skip=False, time=False)
    e += _vae_attn(("encoder", "mid", "attn"), "encoder.mid_block.attentions.0")
    e += _resnet(("encoder", "mid", "resnet2"), "encoder.mid_block.resnets.1",
                 has_skip=False, time=False)
    e += _norm(("encoder", "norm_out"), "encoder.conv_norm_out")
    e += _conv(("encoder", "conv_out"), "encoder.conv_out")
    e += _conv(("encoder", "quant_conv"), "quant_conv")
    if cfg.kind == "vq":
        # diffusers VQModel keeps the codebook at quantize.embedding.
        e.append((("codebook",), "quantize.embedding.weight", "none"))

    e += _conv(("decoder", "post_quant_conv"), "post_quant_conv")
    e += _conv(("decoder", "conv_in"), "decoder.conv_in")
    e += _resnet(("decoder", "mid", "resnet1"), "decoder.mid_block.resnets.0",
                 has_skip=False, time=False)
    e += _vae_attn(("decoder", "mid", "attn"), "decoder.mid_block.attentions.0")
    e += _resnet(("decoder", "mid", "resnet2"), "decoder.mid_block.resnets.1",
                 has_skip=False, time=False)
    in_ch = chs[-1]
    for pos, lvl in enumerate(reversed(range(n))):
        out_ch = chs[lvl]
        for j in range(cfg.layers_per_block + 1):
            e += _resnet(("decoder", "up", pos, "resnets", j),
                         f"decoder.up_blocks.{pos}.resnets.{j}",
                         has_skip=in_ch != out_ch, time=False)
            in_ch = out_ch
        if lvl != 0:
            e += _conv(("decoder", "up", pos, "upsample"),
                       f"decoder.up_blocks.{pos}.upsamplers.0.conv")
    e += _norm(("decoder", "norm_out"), "decoder.conv_norm_out")
    e += _conv(("decoder", "conv_out"), "decoder.conv_out")
    return e


# ---------------------------------------------------------------------------
# Tree navigation + load/export
# ---------------------------------------------------------------------------


def _get(tree: Any, path: Tuple[Any, ...]) -> Any:
    for p in path:
        tree = tree[p]
    return tree


def _set(tree: Any, path: Tuple[Any, ...], value: Any) -> None:
    for p in path[:-1]:
        tree = tree[p]
    tree[path[-1]] = value


def read_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Read a torch ``.bin``/``.pt`` or ``.safetensors`` file to numpy."""
    if path.endswith(".safetensors"):
        from safetensors.numpy import load_file  # optional dependency

        return dict(load_file(path))
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.numpy() for k, v in sd.items()}


def _find_weights_file(dirpath: str, names: Tuple[str, ...]) -> str:
    for n in names:
        p = os.path.join(dirpath, n)
        if os.path.exists(p):
            return p
    raise FileNotFoundError(f"no weights file in {dirpath} (tried {names})")


def apply_state_dict(params: Any, entries: List[Entry],
                     sd: Dict[str, np.ndarray], strict: bool = True) -> Any:
    """Fill our param tree (in place) from a diffusers-named state dict."""
    import jax.numpy as jnp

    missing, used = [], set()
    for our_path, their_name, kind in entries:
        if their_name not in sd:
            missing.append(their_name)
            continue
        w = _FWD[kind](sd[their_name])
        cur = _get(params, our_path)
        if tuple(cur.shape) != tuple(w.shape):
            raise ValueError(
                f"shape mismatch at {'/'.join(map(str, our_path))} ← {their_name}: "
                f"ours {tuple(cur.shape)} vs checkpoint {tuple(w.shape)}")
        _set(params, our_path, jnp.asarray(w, dtype=cur.dtype))
        used.add(their_name)
    if strict:
        if missing:
            raise KeyError(f"checkpoint missing {len(missing)} entries, "
                           f"first: {missing[:5]}")
        unused = [k for k in sd if k not in used
                  and not k.endswith("position_ids")
                  and not k.startswith("to_logits")]
        if unused:
            raise KeyError(f"checkpoint has {len(unused)} unmapped entries, "
                           f"first: {unused[:5]}")
    return params


def export_state_dict(params: Any, entries: List[Entry]) -> Dict[str, np.ndarray]:
    """Inverse of :func:`apply_state_dict` (for tests / parity tooling)."""
    out = {}
    for our_path, their_name, kind in entries:
        w = np.asarray(_get(params, our_path))
        out[their_name] = _INV[kind](w)
    return out


def load_unet(params: Any, cfg: UNetConfig, dirpath: str, strict: bool = True) -> Any:
    sd = read_state_dict(_find_weights_file(
        dirpath, ("diffusion_pytorch_model.safetensors", "diffusion_pytorch_model.bin")))
    return apply_state_dict(params, unet_entries(cfg), sd, strict)


def load_text_encoder(params: Any, cfg: TextEncoderConfig, dirpath: str,
                      strict: bool = True) -> Any:
    sd = read_state_dict(_find_weights_file(
        dirpath, ("model.safetensors", "pytorch_model.bin")))
    entries = (ldm_text_encoder_entries(cfg) if cfg.arch == "ldmbert"
               else text_encoder_entries(cfg))
    return apply_state_dict(params, entries, sd, strict)


def load_vae(params: Any, cfg: VAEConfig, dirpath: str, strict: bool = True) -> Any:
    sd = read_state_dict(_find_weights_file(
        dirpath, ("diffusion_pytorch_model.safetensors", "diffusion_pytorch_model.bin")))
    return apply_state_dict(params, vae_entries(cfg), sd, strict)


def _find_subdir(checkpoint_dir: str, names: Tuple[str, ...]) -> str:
    for n in names:
        p = os.path.join(checkpoint_dir, n)
        if os.path.isdir(p):
            return p
    raise FileNotFoundError(
        f"no {'/'.join(names)} directory in {checkpoint_dir}")


def one_tower(config):
    """The text tower's configuration of a preset this module has key maps
    for: one tower, no added embedding."""
    if len(config.towers) != 1 or config.unet.addition_embed_in is not None:
        raise NotImplementedError(
            f"preset {config.name!r}: loading a checkpoint of several text "
            "towers and an added embedding waits until such files are on "
            "this machine (it takes key maps for a second tower and for "
            "`add_embedding`)")
    return config.text


def load_pipeline(checkpoint_dir: str, config, tokenizer=None):
    """Load a full checkpoint directory into a Pipeline.

    Accepts both diffusers layouts: SD repos (``unet/``, ``text_encoder/``,
    ``vae/``, ``tokenizer/``) and the CompVis LDM repo's naming (``bert/``,
    ``vqvae/``) — the two directory trees the reference's
    ``from_pretrained`` calls resolve (`/root/reference/main.py:29`,
    LDM per SURVEY §3.3)."""
    import jax

    from ..engine.sampler import Pipeline
    from ..utils.tokenizer import ClipBpeTokenizer
    from .denoiser import require_unet
    from .text_encoder import init_text_encoder
    from .unet import init_unet
    from . import vae as vae_mod

    require_unet(config.unet, "the checkpoint key map")
    text = one_tower(config)
    unet_params = load_unet(init_unet(jax.random.PRNGKey(0), config.unet),
                            config.unet, _find_subdir(checkpoint_dir, ("unet",)))
    text_params = load_text_encoder(
        init_text_encoder(jax.random.PRNGKey(0), text), text,
        _find_subdir(checkpoint_dir, ("text_encoder", "bert")))
    vae_params = load_vae(vae_mod.init_vae(jax.random.PRNGKey(0), config.vae),
                          config.vae, _find_subdir(checkpoint_dir, ("vae", "vqvae")))
    if tokenizer is None:
        tok_dir = os.path.join(checkpoint_dir, "tokenizer")
        max_len = text.max_length
        if text.arch == "ldmbert":
            from ..utils.tokenizer import BertWordPieceTokenizer

            tokenizer = BertWordPieceTokenizer.from_dir(
                tok_dir, model_max_length=max_len)
        else:
            tokenizer = ClipBpeTokenizer.from_dir(
                tok_dir, model_max_length=max_len)
    return Pipeline(config=config, unet_params=unet_params,
                    text_params=text_params, vae_params=vae_params,
                    tokenizer=tokenizer)
