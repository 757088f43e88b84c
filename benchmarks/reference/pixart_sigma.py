"""Plain reference of a prompt-to-prompt edit for PixArt-Sigma at 1024 x 1024:
a transformer denoiser over patch tokens with adaLN-single, conditioned on
T5-v1.1-XXL's encoder over a masked caption, decoded by SDXL's autoencoder.

Written from the papers and the published configuration files, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision,
with no kernels, no cache and no batching over edit groups. It imports
nothing of the program and nothing of the other references (it repeats them
where the mathematics is the same). It reads the sizes from the
configuration's JSON file and the weights from the tree the benchmark made
from ``--seed`` (``lib/weights.py``); token ids, the caption's key mask, the
alignment of the two prompts and the starting noise it works out itself.

Sources: Chen et al., "PixArt-Sigma: Weak-to-Strong Training of Diffusion
Transformer for 4K Text-to-Image Generation" (arXiv 2403.04692) and
"PixArt-alpha" (arXiv 2310.00426), section 2.3 (the block, adaLN-single);
``transformer/config.json`` of ``PixArt-alpha/PixArt-Sigma-XL-2-1024-MS``;
``text_encoder/config.json``, ``vae/config.json`` and
``scheduler/scheduler_config.json`` of
``PixArt-alpha/pixart_sigma_sdxlvae_T5_diffusers``; Raffel et al., "Exploring
the Limits of Transfer Learning with a Unified Text-to-Text Transformer"
(JMLR 2020) for T5's encoder and its relative positions; Rombach et al.
(CVPR 2022) for the autoencoder; Song et al., "Denoising Diffusion Implicit
Models" (ICLR 2021), eq. 12 with sigma = 0; Ho & Salimans 2022 for
classifier-free guidance; Hertz et al., "Prompt-to-Prompt Image Editing with
Cross Attention Control" (2022), section 3.

The model, as equations (ids ``t`` (300,) of one prompt, ``d`` = 1152):

1. Caption. ``m_j = 1`` for every token up to and including the first
   end-of-text id, else 0. T5's encoder: ``h = E[t]``; 24 times ``h += O
   softmax(Q K^T + B + M) V`` of ``rms(h)`` (no 1/sqrt(d) scale; ``B`` the
   relative-position bias of T5's bidirectional buckets, one table for all
   layers; ``M`` 0 at a kept key and -inf at a masked one) and ``h += W_o
   (gelu_tanh(W_0 rms(h)) * W_1 rms(h))``; then ``rms(h)``, where ``rms(x) =
   g x / sqrt(mean(x^2) + 1e-6)``.
2. Tokens. The latent (128, 128, 4) is cut into 2 x 2 patches by a stride-2
   convolution with bias: 4,096 tokens of ``d``, plus the fixed 2-D sin-cos
   positions on a grid of 64 (coordinates ``i / 2``), the first half of the
   width embedding the column, the second the row.
3. Time. ``e`` = [cos | sin] of ``t`` times ``10000^(-k/128)``, 256 wide;
   ``temb = L2 silu(L1 e)``; ``t6 = W silu(temb)``, six rows of ``d``.
4. Caption projection. ``c = L2 gelu_tanh(L1 h)``: (300, d).
5. Block (28): ``(sh1, sc1, g1, sh2, sc2, g2) = T_i + t6``;
   ``x += g1 SelfAttn(LN(x)(1 + sc1) + sh1)``; ``x += CrossAttn(x, c)`` with
   -10000 added to the logits of masked caption keys (the tokens are not
   normalised first); ``x += g2 FF(LN(x)(1 + sc2) + sh2)``, ``FF`` = d ->
   4d, gelu_tanh, -> d. ``LN`` has no affine, eps 1e-6; attention has 16
   heads of 72 with biases.
6. Output. ``(sh, sc) = T_f + temb``; ``y = W(LN(x)(1 + sc) + sh)``, 2 x 2 x 8
   a token, put back in place: (128, 128, 8). The first 4 channels are the
   noise prediction; the other 4 (the learned variance) are dropped.
7. Unconditional branch: the empty prompt through the same encoder, under
   its own mask.

Departures from the published description, all inherited from the
configuration as the program runs it and listed under ``assumed`` in the
file: the hash word tokenizer stands in for T5's sentencepiece (one token
per word of at most 8 letters, a BOS id 0 ahead of the words); DDIM (eta 0,
``steps_offset`` 0) over the checkpoint's linear betas stands in for its
DPM-Solver++; GroupNorm epsilon is 1e-5 in the autoencoder.

Kernels arrive in the type they are served in (bfloat16 in this
configuration). Each is widened to float32 where it is used and nowhere
else.

Departure of this file from "no blocking": the probabilities of a
self-attention site (4 x 16 x 4096^2 x 4 B = 4.3 GB) and of the
autoencoder's one attention (16,384 keys) do not fit, so wherever they would
take more than ``PROBS_BYTES`` the site is computed over blocks of queries
(``blocked_attention``), and at an edited self site the control is applied
to each block's maps. Softmax runs along the keys, so each query's row is
whole inside its block: the same arithmetic in the same order, only never
all rows at once. The cross sites stay whole. The images are decoded one at
a time.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Prompts: token ids, the key mask and the alignment of an edit pair
# ---------------------------------------------------------------------------

BOS, EOS = 0, 1


def _length(cfg: dict) -> int:
    return cfg["text_encoder"]["max_position_embeddings"]


def token_ids(cfg: dict, prompt: str) -> np.ndarray:
    """Hash word tokenizer: BOS, one id per word, EOS, padded with EOS."""
    vocab = cfg["text_encoder"]["vocab_size"]
    length = _length(cfg)
    ids = [BOS]
    for word in prompt.lower().split():
        if len(word) > 8:
            raise ValueError(f"word {word!r} is longer than one token")
        h = hashlib.sha1(word.encode("utf-8")).digest()
        ids.append(2 + int.from_bytes(h[:4], "big") % (vocab - 2))
    ids.append(EOS)
    if len(ids) > length:
        raise ValueError("prompt longer than the context")
    return np.asarray(ids + [EOS] * (length - len(ids)), np.int32)


def key_mask(ids):
    """1 up to and including the first end-of-text id of each row."""
    eos = (ids == EOS).astype(jnp.int32)
    return (jnp.cumsum(eos, axis=-1) - eos == 0).astype(jnp.float32)


def replace_matrix(cfg: dict, source: str, target: str) -> np.ndarray:
    """Word swap: token j of the target takes the attention of token j of
    the source. With one token per word and equal word counts that is the
    identity over the caption (P2P section 3.2, "word swap")."""
    if len(source.split()) != len(target.split()):
        raise ValueError("word swap needs prompts of equal length")
    return np.eye(_length(cfg), dtype=np.float32)


def refine_alignment(cfg: dict, source: str, target: str):
    """Adding a phrase: for every target token the source token it came
    from, and 1 where there is one (P2P section 3.2, "adding a new phrase")."""
    length = _length(cfg)
    src = ["<bos>"] + source.lower().split() + ["<eos>"]
    tgt = ["<bos>"] + target.lower().split() + ["<eos>"]
    index = np.arange(length, dtype=np.int32)
    exists = np.ones(length, np.float32)
    j = 0
    for i, word in enumerate(tgt):
        if j < len(src) and src[j] == word:
            index[i] = j
            j += 1
        else:
            index[i], exists[i] = 0, 0.0
    if j != len(src):
        raise ValueError("target does not contain the source in order")
    return index, exists


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def wide(kernel):
    """A kernel as it is multiplied: float32, widened here, at its use."""
    return kernel.astype(jnp.float32)


def linear(p, x):
    y = jnp.matmul(x, wide(p["kernel"]))
    return y + p["bias"] if "bias" in p else y


def conv(p, x, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, wide(p["kernel"]), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def group_norm(p, x, groups, eps):
    c = x.shape[-1]
    xg = x.reshape(x.shape[0], -1, groups, c // groups)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(x.shape) * p["scale"] + p["bias"]


def plain_norm(x, eps=1e-6):
    """LayerNorm without an affine."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def rms_norm(p, x, eps=1e-6):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def softmax(x):
    x = x - x.max(-1, keepdims=True)
    e = jnp.exp(x)
    return e / e.sum(-1, keepdims=True)


def split_heads(x, heads):
    b, n, c = x.shape
    return x.reshape(b, n, heads, c // heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def sinusoidal(t, dim):
    """[cos | sin] of t times 10000^(-i / half), i < half = dim / 2."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# T5 encoder (equation 1)
# ---------------------------------------------------------------------------


def t5_buckets(n: int, num_buckets: int, max_distance: int) -> np.ndarray:
    """(n, n): bucket of key j seen from query i. Half the buckets for
    keys after the query; in each half exact distances below a quarter of
    the buckets, logarithmic ones from there to ``max_distance``."""
    buckets = np.zeros((n, n), np.int32)
    half = num_buckets // 2
    exact = half // 2
    for i in range(n):
        for j in range(n):
            rel = j - i
            b = half if rel > 0 else 0
            dist = abs(rel)
            if dist < exact:
                b += dist
            else:
                b += min(half - 1, exact + int(
                    math.log(dist / exact) / math.log(max_distance / exact)
                    * (half - exact)))
            buckets[i, j] = b
    return buckets


def t5_encoder(w, tc: dict, ids, mask):
    """The final RMS norm's output (B, L, 4096) of ``ids`` (B, L)."""
    n = ids.shape[1]
    heads = tc["num_attention_heads"]
    buckets = t5_buckets(n, tc["relative_attention_num_buckets"],
                         tc["relative_attention_max_distance"])
    bias = w["relative_attention_bias"][buckets].transpose(2, 0, 1)[None]
    bias = bias + jnp.where(mask[:, None, None, :] > 0, 0.0, -jnp.inf)
    h = w["token_embed"][ids]
    for layer in w["layers"]:
        x = rms_norm(layer["ln1"], h)
        q, k, v = (split_heads(linear(layer[n_], x), heads) for n_ in "qkv")
        probs = softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) + bias)
        h = h + linear(layer["out"],
                       merge_heads(jnp.einsum("bhqk,bhkd->bhqd", probs, v)))
        x = rms_norm(layer["ln2"], h)
        h = h + linear(layer["wo"], gelu_tanh(linear(layer["wi_0"], x))
                       * linear(layer["wi_1"], x))
    return rms_norm(w["final_ln"], h)


# ---------------------------------------------------------------------------
# Denoiser (equations 2-6)
# ---------------------------------------------------------------------------


def positions(grid: int, width: int, interpolation_scale: float):
    """(grid^2, width) fixed 2-D sin-cos positions, row-major tokens: the
    first half embeds the token's column, the second its row, each
    [sin | cos] of the coordinate times 10000^(-k / (width / 4))."""
    quarter = width // 4
    omega = 1.0 / 10000.0 ** (np.arange(quarter, dtype=np.float64) / quarter)
    coord = np.arange(grid, dtype=np.float64) / interpolation_scale
    col = np.tile(coord, grid)                  # token i*grid + j: column j
    row = np.repeat(coord, grid)                # ... row i

    def one(pos):
        a = pos[:, None] * omega[None]
        return np.concatenate([np.sin(a), np.cos(a)], axis=1)

    return jnp.asarray(np.concatenate([one(col), one(row)], axis=1), jnp.float32)


class Control:
    """The prompt-to-prompt edit of one group ``[source, target]`` as the
    sampler applies it at an attention layer. ``step`` is traced."""

    def __init__(self, kind, cross_end, self_start, self_end, self_max_pixels,
                 mapper=None, index=None, exists=None):
        self.kind = kind
        self.cross_end, self.self_start, self.self_end = \
            cross_end, self_start, self_end
        self.self_max_pixels = self_max_pixels
        self.mapper, self.index, self.exists = mapper, index, exists

    def __call__(self, probs, step, is_cross):
        """probs: (2B, heads, P, K), unconditional half first."""
        b = probs.shape[0] // 2
        base, edits = probs[b], probs[b + 1:]
        if is_cross:
            if self.kind == "replace":
                new = jnp.einsum("hpw,wn->hpn", base, self.mapper)[None]
                new = jnp.broadcast_to(new, edits.shape)
            else:
                new = (base[..., self.index] * self.exists)[None] \
                    + edits * (1.0 - self.exists)
            edits = jnp.where(step < self.cross_end, new, edits)
        else:
            inside = (step >= self.self_start) & (step < self.self_end)
            edits = jnp.where(inside, jnp.broadcast_to(base[None], edits.shape),
                              edits)
        return jnp.concatenate([probs[:b + 1], edits], axis=0)


#: Most bytes of float32 probabilities computed at once at a self site.
PROBS_BYTES = 2 ** 28


def attention_probs(q, k, bias=0.0):
    scale = q.shape[-1] ** -0.5
    return softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias)


def blocked_attention(q, k, v, edit=None):
    """softmax(Q K^T / sqrt(d)) V over blocks of queries: the largest block
    that divides the queries and keeps the block's probabilities, for the
    whole batch and all heads, within ``PROBS_BYTES``. ``edit`` (probs ->
    probs), where given, is applied to each block's maps."""
    b, h, p, d = q.shape
    row_bytes = b * h * k.shape[2] * 4
    block = max(n for n in range(1, p + 1)
                if p % n == 0 and (n * row_bytes <= PROBS_BYTES or n == 1))

    def one(q_block):
        probs = attention_probs(q_block, k)
        if edit is not None:
            probs = edit(probs)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    if block == p:
        return one(q)
    blocks = q.reshape(b, h, p // block, block, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, blocks)                       # (n, b, h, block, d)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, p, d)


def self_attention(p, x, heads, control, step):
    q, k, v = (split_heads(linear(p[n_], x), heads)
               for n_ in ("to_q", "to_k", "to_v"))
    edit = None
    if control is not None and q.shape[2] <= control.self_max_pixels:
        def edit(probs):
            return control(probs, step, False)
    return linear(p["to_out"], merge_heads(blocked_attention(q, k, v, edit)))


def cross_attention(p, x, caption, bias, heads, control, step):
    q = split_heads(linear(p["to_q"], x), heads)
    k = split_heads(linear(p["to_k"], caption), heads)
    v = split_heads(linear(p["to_v"], caption), heads)
    probs = attention_probs(q, k, bias)
    if control is not None:
        probs = control(probs, step, True)
    return linear(p["to_out"], merge_heads(jnp.einsum("bhqk,bhkd->bhqd", probs, v)))


def denoiser(w, tc: dict, x, t, caption, mask, control=None, step=None):
    """ε of the latents ``x`` (B, 128, 128, 4) at step ``t`` given the
    caption's T5 states (B, 300, 4096) and key mask (B, 300)."""
    b, size, _, _ = x.shape
    p = tc["patch_size"]
    heads = tc["num_attention_heads"]
    d = heads * tc["attention_head_dim"]
    grid = size // p
    h = conv(w["patch_embed"], x, stride=p, padding="VALID").reshape(b, grid * grid, d)
    h = h + positions(grid, d, tc["interpolation_scale"])[None]
    t = jnp.broadcast_to(t, (b,))
    temb = linear(w["time_embed"]["linear_2"],
                  silu(linear(w["time_embed"]["linear_1"], sinusoidal(t, 256))))
    t6 = linear(w["t_block"], silu(temb)).reshape(b, 6, d)
    c = linear(w["caption_proj"]["linear_2"],
               gelu_tanh(linear(w["caption_proj"]["linear_1"], caption)))
    bias = ((1.0 - mask) * -10000.0)[:, None, None, :]
    if len(w["blocks"]) != tc["num_layers"]:
        raise ValueError(f"{len(w['blocks'])} blocks where the configuration "
                         f"states {tc['num_layers']}")
    for blk in w["blocks"]:
        mod = blk["scale_shift_table"][None] + t6
        sh1, sc1, g1, sh2, sc2, g2 = (mod[:, i:i + 1] for i in range(6))
        h = h + g1 * self_attention(blk["self_attn"],
                                    plain_norm(h) * (1 + sc1) + sh1, heads,
                                    control, step)
        h = h + cross_attention(blk["cross_attn"], h, c, bias, heads, control,
                                step)
        y = plain_norm(h) * (1 + sc2) + sh2
        h = h + g2 * linear(blk["ff_out"], gelu_tanh(linear(blk["ff_in"], y)))
    table = w["final"]["scale_shift_table"]
    shift, scale = table[0] + temb[:, None], table[1] + temb[:, None]
    y = linear(w["final"]["proj_out"], plain_norm(h) * (1 + scale) + shift)
    out_ch = tc["out_channels"]
    y = y.reshape(b, grid, grid, p, p, out_ch).transpose(0, 1, 3, 2, 4, 5)
    y = y.reshape(b, size, size, out_ch)
    return y[..., :tc["in_channels"]]


# ---------------------------------------------------------------------------
# Autoencoder decoder
# ---------------------------------------------------------------------------


def res_block(p, x, groups):
    h = conv(p["conv1"], silu(group_norm(p["norm1"], x, groups, 1e-5)))
    h = conv(p["conv2"], silu(group_norm(p["norm2"], h, groups, 1e-5)))
    if "skip" in p:
        x = conv(p["skip"], x)
    return x + h


def decode(w, cfg: dict, latents):
    """One latent (1, h, w, c) to its image."""
    vc = cfg["vae"]
    g = vc["norm_num_groups"]
    p = w["decoder"]
    h = latents / vc["scaling_factor"]
    h = conv(p["post_quant_conv"], h)
    h = conv(p["conv_in"], h)
    h = res_block(p["mid"]["resnet1"], h, g)
    a = p["mid"]["attn"]
    b, hh, ww, c = h.shape
    y = group_norm(a["norm"], h, g, 1e-5).reshape(b, hh * ww, c)
    q, k, v = (linear(a[n_], y)[:, None] for n_ in "qkv")
    out = blocked_attention(q, k, v)[:, 0]
    h = h + linear(a["out"], out).reshape(b, hh, ww, c)
    h = res_block(p["mid"]["resnet2"], h, g)
    for block in p["up"]:
        for rp in block["resnets"]:
            h = res_block(rp, h, g)
        if "upsample" in block:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv(block["upsample"], h)
    return conv(p["conv_out"], silu(group_norm(p["norm_out"], h, g, 1e-5)))


def to_uint8(image):
    """(x / 2 + 0.5).clamp(0, 1) * 255 as uint8, as the paper's code saves."""
    return (jnp.clip(image / 2.0 + 0.5, 0.0, 1.0) * 255.0).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Sampler
# ---------------------------------------------------------------------------


def ddim_tables(cfg: dict, num_steps: int):
    sc = cfg["scheduler"]
    n = sc["num_train_timesteps"]
    if sc["beta_schedule"] != "linear":
        raise ValueError(sc["beta_schedule"])
    betas = np.linspace(sc["beta_start"], sc["beta_end"], n, dtype=np.float64)
    acp = np.cumprod(1.0 - betas)
    stride = n // num_steps
    ts = (np.arange(num_steps) * stride)[::-1] + sc["steps_offset"]
    prev = ts - stride
    final = 1.0 if sc["set_alpha_to_one"] else acp[0]
    a_t = acp[ts]
    a_prev = np.where(prev >= 0, acp[np.clip(prev, 0, n - 1)], final)
    return (jnp.asarray(ts, jnp.int32), jnp.asarray(a_t, jnp.float32),
            jnp.asarray(a_prev, jnp.float32))


def ddim_update(x, eps, a_t, a_prev):
    """x_t -> x_prev from the (guided) noise prediction (DDIM eq. 12)."""
    x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
    return jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps


def alignment(cfg: dict, edit: dict, prompts) -> dict:
    """The arrays that tie the target's tokens to the source's."""
    if len(prompts) != 2:
        raise ValueError("the reference edits one target against one source")
    if edit["kind"] == "replace":
        return {"mapper": replace_matrix(cfg, *prompts)}
    if edit["kind"] == "refine":
        index, exists = refine_alignment(cfg, *prompts)
        return {"index": index, "exists": exists}
    raise ValueError(edit["kind"])


def prompt_ids(cfg: dict, prompts) -> np.ndarray:
    """Ids of the empty prompt, once per image, then of the prompts."""
    return np.stack([token_ids(cfg, "")] * len(prompts)
                    + [token_ids(cfg, p) for p in prompts])


def make_edit_fn(cfg: dict, edit: dict):
    """``f(weights, x_T, ids, align) -> (images in [-1, 1], final latents)``
    for one edit group ``[source, target]`` from the shared noise ``x_T``
    (1, h, w, c), jitted once for all prompt pairs of a cell.

    ``edit``: kind, num_steps, guidance_scale, cross_replace_steps,
    self_replace_steps, self_max_pixels (fractions of the step count, the
    cross window of num_steps + 1 as in the paper's code). No phase gate.
    """
    if edit.get("gate") is not None:
        raise ValueError("this reference samples every step in full")
    n = edit["num_steps"]
    guidance = edit["guidance_scale"]
    tc = cfg["transformer"]
    ts, a_t, a_prev = ddim_tables(cfg, n)
    b = 2

    def run(weights, x_T, ids, align):
        control = Control(edit["kind"],
                          int(edit["cross_replace_steps"] * (n + 1)), 0,
                          int(edit["self_replace_steps"] * n),
                          edit["self_max_pixels"], **align)
        mask = key_mask(ids)
        caption = t5_encoder(weights["text"], cfg["text_encoder"], ids, mask)
        x = jnp.broadcast_to(x_T, (b,) + x_T.shape[1:])

        def step_fn(x, inp):
            step, t, at, ap = inp
            out = denoiser(weights["unet"], tc, jnp.concatenate([x, x]), t,
                           caption, mask, control, step)
            eps = out[:b] + guidance * (out[b:] - out[:b])
            return ddim_update(x, eps, at, ap), None

        x, _ = jax.lax.scan(step_fn, x,
                            (jnp.arange(n, dtype=jnp.int32), ts, a_t, a_prev))
        image = jnp.concatenate([decode(weights["vae"], cfg, x[i:i + 1])
                                 for i in range(b)])
        return image, x

    jitted = jax.jit(run)

    def at_highest(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)

    return at_highest


def noise(key_data, shape):
    """The starting noise of a call: standard normal from the call's key."""
    return jax.random.normal(jnp.asarray(key_data, jnp.uint32), shape,
                             jnp.float32)
