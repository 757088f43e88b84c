"""Operations and bytes the configuration's mathematics needs, counted from
the sizes in its file. A multiply-add counts as 2 operations; only matrix
multiplications and convolutions are counted (norms, activations, softmax and
the sampler's arithmetic are left out, well under 1 % of the total), so a
share of the peak worked out from these is a little low, never high.

The U-Net is counted as a member of diffusers' ``UNet2DConditionModel``
family, from keys that may be absent (PR 33): ``unet.transformer_depth`` is
one int (every attentive level and the mid block) or a list with one int a
level (0: that level has no transformer; the mid block takes the last
level's); ``text_encoder`` is one tower or a list of towers, summed;
``unet.addition_embed_in`` is the width of a vector embedded by two linear
layers and added to the time embedding.

A file has either a ``unet`` block or a ``transformer`` block: a
transformer denoiser over patch tokens (diffusers' ``Transformer2DModel`` /
``PixArtTransformer2DModel`` names, ``lib/pipeline.py`` states the contract),
counted by ``transformer_forward_flops``. A text tower whose dict has
``intermediate_size`` has a feed-forward that wide (in place of
``ff_mult`` times the hidden size), gated where ``hidden_act`` begins with
``gated-`` (T5's ``feed_forward_proj``): three matrices, not two.
"""

from __future__ import annotations

import math


def _conv(hw: int, cin: int, cout: int, k: int = 3, stride: int = 1) -> int:
    """A k x k convolution onto ``hw`` output pixels of a square map, padded
    by k // 2. Taps that fall on the zero padding are not counted (as XLA's
    own count leaves them out): 3 n - 2 of 3 n per side at stride 1, 3 n - 1
    at stride 2."""
    side = math.isqrt(hw)
    taps = hw if k == 1 else (3 * side - (2 if stride == 1 else 1)) ** 2
    return 2 * taps * cin * cout


def res_block_flops(hw: int, cin: int, cout: int, temb: int = 0) -> int:
    f = _conv(hw, cin, cout) + _conv(hw, cout, cout) + 2 * temb * cout
    if cin != cout:
        f += _conv(hw, cin, cout, 1)
    return f


def self_attention_flops(pixels: int, channels: int) -> int:
    """Projections q, k, v, out, then Q K^T and P V, for one image."""
    return 4 * 2 * pixels * channels * channels + 2 * 2 * pixels * pixels * channels


def cross_attention_flops(pixels: int, channels: int, ctx_len: int,
                          ctx_dim: int) -> int:
    """q and out over the pixels, k and v over the context, then the two
    products over the context's tokens, for one image."""
    return (2 * 2 * pixels * channels * channels
            + 2 * 2 * ctx_len * ctx_dim * channels
            + 2 * 2 * pixels * ctx_len * channels)


def depth_at(uc: dict, lvl: int) -> int:
    """Transformer blocks of one spatial transformer at level ``lvl``; 0
    where the level has none (``attention_levels`` says so, or its depth)."""
    depth = uc["transformer_depth"]
    if not uc["attention_levels"][lvl]:
        return 0
    return depth if isinstance(depth, int) else depth[lvl]


def mid_depth(uc: dict) -> int:
    """The mid block's: the last level's entry, or the one int."""
    depth = uc["transformer_depth"]
    return depth if isinstance(depth, int) else depth[-1]


def transformer_flops(uc: dict, hw: int, c: int, cross: bool = True,
                      depth: int = 1) -> int:
    """One spatial transformer of ``depth`` blocks; nothing where it has
    none (a level without attention)."""
    if not depth:
        return 0
    f = 2 * _conv(hw, c, c, 1)                          # proj_in, proj_out
    per_block = self_attention_flops(hw, c)
    if cross:
        per_block += cross_attention_flops(hw, c, uc["context_len"],
                                           uc["cross_attention_dim"])
    inner = c * uc["ff_mult"]
    per_block += 2 * hw * c * 2 * inner + 2 * hw * inner * c     # GEGLU
    return f + depth * per_block


def unet_sites(uc: dict):
    """The transformer blocks in call order, each a self site and then a
    cross site: (place, level, pixels, channels), ``depth`` of them a
    spatial transformer (the order of ``models/config.py:unet_attn_specs``)."""
    levels = len(uc["block_out_channels"])
    out = []
    for lvl in range(levels):
        out += [("down", lvl)] * uc["layers_per_block"] * depth_at(uc, lvl)
    out += [("mid", levels - 1)] * mid_depth(uc)
    for lvl in reversed(range(levels)):
        out += [("up", lvl)] * (uc["layers_per_block"] + 1) * depth_at(uc, lvl)
    return [(place, lvl, (uc["sample_size"] >> lvl) ** 2,
             uc["block_out_channels"][lvl]) for place, lvl in out]


def self_site_names(block: dict):
    """The self sites' scope names in call order, as the program builds them:
    the place and the site's index among all attention sites (a block's self
    site, then its cross site). ``block`` is the file's ``unet`` or
    ``transformer`` block; a transformer's place is ``block``: its block
    ``i`` has the self site ``block<2i>`` and the cross site ``block<2i+1>``."""
    if is_transformer(block):
        return [f"block{2 * i}" for i in range(block["num_layers"])]
    return [f"{place}{2 * i}" for i, (place, *_) in enumerate(unet_sites(block))]


def is_transformer(block: dict) -> bool:
    """Whether a denoiser block is a transformer's (it has patches)."""
    return "patch_size" in block


def denoiser(config: dict) -> dict:
    """The file's denoiser block: ``transformer`` where it has one, else
    ``unet``."""
    return config["transformer"] if "transformer" in config else config["unet"]


def transformer_tokens(tc: dict) -> int:
    """Patch tokens of one row: the latent's side over the patch, squared."""
    return (tc["sample_size"] // tc["patch_size"]) ** 2


def transformer_block_flops(tc: dict, cross: bool = True) -> int:
    """One transformer block for one row: self-attention over the patch
    tokens, cross-attention over all ``context_len`` caption tokens (masked
    keys are counted: the products run over all of them), the feed-forward
    ``ff_mult`` wide (two matrices; a gated activation, ``geglu``, three).
    The block's adaLN modulation adds a table to the time vector: no
    product."""
    p, c = transformer_tokens(tc), tc["num_attention_heads"] * tc["attention_head_dim"]
    f = self_attention_flops(p, c)
    if cross:
        f += cross_attention_flops(p, c, tc["context_len"], tc["cross_attention_dim"])
    matrices = 3 if tc["activation_fn"] == "geglu" else 2
    return f + matrices * 2 * p * c * c * tc["ff_mult"]


def transformer_forward_flops(tc: dict, cross: bool = True) -> int:
    """One forward of a transformer denoiser for one row: the patch
    embedding (a ``patch_size`` convolution of that stride), the caption
    projection (two linear layers over the ``context_len`` tokens,
    ``caption_channels`` in), the timestep MLP (256 sinusoids, two linear
    layers) and adaLN-single's ``t_block`` (one linear layer to six times
    the width), with ``use_additional_conditions`` the resolution (two rows)
    and aspect-ratio (one row) embeddings a third of the width wide, then
    ``num_layers`` blocks and the final projection to the patches'
    ``out_channels``. ``cross=False`` leaves out the cross-attention and the
    caption projection that only it reads (served from a cache)."""
    p, c = transformer_tokens(tc), tc["num_attention_heads"] * tc["attention_head_dim"]
    patch = tc["patch_size"] ** 2
    f = 2 * p * tc["in_channels"] * patch * c                   # patch embedding
    f += 2 * 256 * c + 2 * c * c + 2 * c * 6 * c                # time MLP, t_block
    if tc["use_additional_conditions"]:
        f += 3 * (2 * 256 * (c // 3) + 2 * (c // 3) ** 2)
    if cross:
        f += 2 * tc["context_len"] * (tc["caption_channels"] * c + c * tc["cross_attention_dim"])
    f += tc["num_layers"] * transformer_block_flops(tc, cross)
    return f + 2 * p * c * patch * tc["out_channels"]          # final layer


def unet_forward_flops(uc: dict, cross: bool = True) -> int:
    """One forward of the U-Net for one row of its batch. ``cross=False``
    leaves out the cross-attention (served from a cache past a phase gate)."""
    chs = uc["block_out_channels"]
    temb = chs[0] * 4
    side = uc["sample_size"]
    f = 2 * chs[0] * temb + 2 * temb * temb
    if uc.get("addition_embed_in"):
        f += 2 * uc["addition_embed_in"] * temb + 2 * temb * temb
    f += _conv(side * side, uc["in_channels"], chs[0])
    skips = [chs[0]]
    cin = chs[0]
    for lvl, cout in enumerate(chs):
        hw = (side >> lvl) ** 2
        for _ in range(uc["layers_per_block"]):
            f += res_block_flops(hw, cin, cout, temb)
            f += transformer_flops(uc, hw, cout, cross, depth_at(uc, lvl))
            cin = cout
            skips.append(cout)
        if lvl != len(chs) - 1:
            f += _conv((side >> (lvl + 1)) ** 2, cout, cout, stride=2)
            skips.append(cout)
    hw = (side >> (len(chs) - 1)) ** 2
    f += 2 * res_block_flops(hw, chs[-1], chs[-1], temb)
    f += transformer_flops(uc, hw, chs[-1], cross, mid_depth(uc))
    for lvl in reversed(range(len(chs))):
        cout = chs[lvl]
        hw = (side >> lvl) ** 2
        for _ in range(uc["layers_per_block"] + 1):
            f += res_block_flops(hw, cin + skips.pop(), cout, temb)
            f += transformer_flops(uc, hw, cout, cross, depth_at(uc, lvl))
            cin = cout
        if lvl != 0:
            f += _conv(4 * hw, cout, cout)
    return f + _conv(side * side, chs[0], uc["out_channels"])


def text_encoder_flops(tc) -> int:
    """One prompt through the text tower, or through each of a list."""
    if isinstance(tc, (list, tuple)):
        return sum(text_encoder_flops(t) for t in tc)
    n, d, inner = (tc["max_position_embeddings"], tc["hidden_size"],
                   tc["attention_inner_dim"])
    if "intermediate_size" in tc:
        gated = tc["hidden_act"].startswith("gated-")
        ff = (3 if gated else 2) * 2 * n * d * tc["intermediate_size"]
    else:
        ff = 2 * 2 * n * d * d * tc["ff_mult"]
    per_layer = 4 * 2 * n * d * inner + 2 * 2 * n * n * inner + ff
    return tc["num_hidden_layers"] * per_layer


def decode_flops(vc: dict, latent_side: int) -> int:
    """One latent through the autoencoder's decoder."""
    chs = [vc["base_channels"] * m for m in vc["channel_mults"]]
    top, lat = chs[-1], vc["latent_channels"]
    hw = latent_side ** 2
    f = _conv(hw, lat, lat, 1) + _conv(hw, lat, top)
    if vc["kind"] == "vq":
        f += 2 * hw * lat * vc["num_codebook"]
    f += 2 * res_block_flops(hw, top, top)
    f += 4 * 2 * hw * top * top + 2 * 2 * hw * hw * top
    cin = top
    for lvl in reversed(range(len(chs))):
        for _ in range(vc["layers_per_block"] + 1):
            f += res_block_flops(hw, cin, chs[lvl])
            cin = chs[lvl]
        if lvl != 0:
            hw *= 4
            f += _conv(hw, cin, cin)
    return f + _conv(hw, chs[0], vc["in_channels"])


def work_flops(config: dict, unet_rows_full: int, unet_rows_cached: int,
               prompts: int, images: int) -> int:
    """All the work of a window: denoiser forwards by rows of their batch
    (with cross-attention, and past a gate without), prompts encoded, images
    decoded. The ``unet_rows_*`` count rows of the denoiser, whichever block
    the file has; the decode takes the latent's side from that block."""
    block = denoiser(config)
    forward = transformer_forward_flops if is_transformer(block) else unet_forward_flops
    return (unet_rows_full * forward(block)
            + unet_rows_cached * forward(block, cross=False)
            + prompts * text_encoder_flops(config["text_encoder"])
            + images * decode_flops(config["vae"], block["sample_size"]))
