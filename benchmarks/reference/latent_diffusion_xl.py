"""Plain reference of a latent-diffusion prompt-to-prompt edit for the SDXL
member of the U-Net family (SDXL-base-1.0 at 1024 x 1024).

Written from the papers and the published configuration files, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision,
with no kernels, no cache and no batching over edit groups. It imports
nothing of the program and nothing of the other references (it repeats them
where the mathematics is the same). It reads the sizes from the
configuration's JSON file and the weights from the tree the benchmark made
from ``--seed`` (``lib/weights.py``); token ids, the alignment of the two
prompts and the starting noise it works out itself.

Sources: Podell et al., "SDXL: Improving Latent Diffusion Models for
High-Resolution Image Synthesis" (arXiv 2307.01952), section 2 and its
appendix, and ``unet/config.json``, ``text_encoder/config.json``,
``text_encoder_2/config.json``, ``vae/config.json`` of
``stabilityai/stable-diffusion-xl-base-1.0``; Rombach et al. (CVPR 2022) for
the U-Net's blocks and the autoencoder; Song et al., "Denoising Diffusion
Implicit Models" (ICLR 2021), eq. 12 with sigma = 0; Ho & Salimans 2022 for
classifier-free guidance; Hertz et al., "Prompt-to-Prompt Image Editing with
Cross Attention Control" (2022), section 3.

What this member adds to the family, as equations (ids ``t`` (77,) of one
prompt; everything else is the block of ``latent_diffusion_v.py`` with other
numbers):

1. Conditioning context. ``h0 = tower0(t)``, ``h1 = tower1(t)``, each the
   residual stream after the tower's last layer but one, with no final
   LayerNorm on it. ``context = concat([h0, h1], -1)``: (77, 768 + 1280).
2. Pooled text. ``p = W_proj LN_final(tower1's last layer's output)[eos]``,
   ``W_proj`` (1280 x 1280, no bias), ``eos`` the position of the first
   end-of-text id.
3. Added embedding. With the sizes ``s = (H_orig, W_orig, top, left, H_target,
   W_target)`` (``size_conditioning`` in the file) and ``e`` the sinusoidal
   embedding of the time step at ``addition_time_embed_dim`` (cos first,
   shift 0): ``a = concat([p, e(s_0), ..., e(s_5)])`` (2816,), ``add = W2
   silu(W1 a + b1) + b2``, and ``emb = time_mlp(e_320(t)) + add`` is what
   every ResNet block takes. ``add`` does not depend on the step.
4. A site group of depth d is ``proj_in``, then d times (``x += self(LN x)``,
   ``x += cross(LN x, context)``, ``x += geglu(LN x)``), then ``proj_out`` and
   the residual: one norm and one pair of projections around d blocks. d is
   ``transformer_depth[level]``, 0 at a level without attention; the mid
   block takes the last level's.
5. Unconditional branch: the same three from the empty prompt.

Departures from the published description, all inherited from the
configuration as the program runs it and listed under ``assumed`` in the
file: the hash word tokenizer stands in for BPE (one token per word of at
most 8 letters); DDIM (eta 0, ``steps_offset`` 0) stands in for the
checkpoint's Euler scheduler; the unconditional branch encodes the empty
prompt where the pipeline's ``force_zeros_for_empty_prompt`` would take
zeros; GroupNorm epsilon is 1e-5 in the autoencoder as in the U-Net's
residual blocks (published 1e-6; the variances are of order 1).

Kernels arrive in the type they are served in (bfloat16 in this
configuration). Each is widened to float32 where it is used and nowhere
else: a float32 copy of the tree would be 13.9 GB.

Departure of this file from "no blocking": the probabilities of the ten
self-attention sites at 4,096 keys (4 x 10 x 4096^2 x 4 B = 2.7 GB) and of
the autoencoder's one attention (16,384 keys) do not fit, so wherever they
would take more than ``PROBS_BYTES`` the site is computed over blocks of
queries (``blocked_attention``). Softmax runs along the keys, so each
query's row is whole inside its block: the same arithmetic in the same
order, only never all rows at once. The edited sites (all cross sites, self
sites up to ``self_max_pixels``) stay whole. The images are decoded one at a
time.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Prompts: token ids and the alignment of an edit pair
# ---------------------------------------------------------------------------

BOS, EOS = 0, 1


def _towers(cfg: dict) -> list:
    return cfg["text_encoder"]


def token_ids(cfg: dict, prompt: str) -> np.ndarray:
    """Hash word tokenizer: BOS, one id per word, EOS, padded with EOS. Both
    towers read the same ids."""
    vocab = _towers(cfg)[0]["vocab_size"]
    length = _towers(cfg)[0]["max_position_embeddings"]
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


def replace_matrix(cfg: dict, source: str, target: str) -> np.ndarray:
    """Word swap: token j of the target takes the attention of token j of
    the source. With one token per word and equal word counts that is the
    identity over the context (P2P section 3.2, "word swap")."""
    if len(source.split()) != len(target.split()):
        raise ValueError("word swap needs prompts of equal length")
    return np.eye(_towers(cfg)[0]["max_position_embeddings"], dtype=np.float32)


def refine_alignment(cfg: dict, source: str, target: str):
    """Adding a phrase: for every target token the source token it came
    from, and 1 where there is one (P2P section 3.2, "adding a new phrase").
    The target holds the source's words in order with words added."""
    length = _towers(cfg)[0]["max_position_embeddings"]
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


def layer_norm(p, x, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


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


def attention_probs(q, k):
    scale = q.shape[-1] ** -0.5
    return softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale)


def sinusoidal(t, dim):
    """[cos | sin] of t times 10000^(-i / half), i < half = dim / 2."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# Text towers (equations 1 and 2)
# ---------------------------------------------------------------------------


def text_tower(w, tc: dict, ids):
    """``(penultimate, last)``: the residual stream after the last layer but
    one and after the last, neither under the final LayerNorm."""
    n = ids.shape[1]
    x = w["token_embed"][ids] + w["pos_embed"][:n]
    mask = 0.0
    if tc["causal"]:
        mask = jnp.where(jnp.arange(n)[None, :] > jnp.arange(n)[:, None],
                         -jnp.inf, 0.0)
    act = (lambda v: v * jax.nn.sigmoid(1.702 * v)) \
        if tc["hidden_act"] == "quick_gelu" else gelu
    heads = tc["num_attention_heads"]
    states = []
    for layer in w["layers"]:
        h = layer_norm(layer["ln1"], x)
        q, k, v = (split_heads(linear(layer[n_], h), heads) for n_ in "qkv")
        scale = q.shape[-1] ** -0.5
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        probs = softmax(logits + mask)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        x = x + linear(layer["out"], merge_heads(out))
        h = layer_norm(layer["ln2"], x)
        x = x + linear(layer["fc2"], act(linear(layer["fc1"], h)))
        states.append(x)
    return states[-2], states[-1]


def conditioning(weights, cfg: dict, ids):
    """``(context, pooled)`` of the prompts ``ids`` (B, 77): equations 1-2."""
    hidden, pooled = [], None
    eos = jnp.argmax(ids == EOS, axis=1)                 # the first of them
    for w, tc in zip(weights, _towers(cfg)):
        penultimate, last = text_tower(w, tc, ids)
        hidden.append(penultimate)
        if tc.get("projection_dim"):
            at_eos = last[jnp.arange(ids.shape[0]), eos]
            pooled = jnp.matmul(layer_norm(w["final_ln"], at_eos),
                                wide(w["projection"]["kernel"]))
    return jnp.concatenate(hidden, axis=-1), pooled


# ---------------------------------------------------------------------------
# U-Net
# ---------------------------------------------------------------------------


def added_embedding(w, cfg: dict, pooled):
    """Equation 3's ``add`` for each row of ``pooled``."""
    sizes = jnp.asarray(cfg["size_conditioning"], jnp.float32)
    e = sinusoidal(sizes, cfg["addition_time_embed_dim"]).reshape(-1)
    a = jnp.concatenate(
        [pooled, jnp.broadcast_to(e, (pooled.shape[0],) + e.shape)], axis=-1)
    return linear(w["add_fc2"], silu(linear(w["add_fc1"], a)))


def res_block(p, x, temb, groups):
    h = conv(p["conv1"], silu(group_norm(p["norm1"], x, groups, 1e-5)))
    if temb is not None:
        h = h + linear(p["time_proj"], silu(temb))[:, None, None, :]
    h = conv(p["conv2"], silu(group_norm(p["norm2"], h, groups, 1e-5)))
    if "skip" in p:
        x = conv(p["skip"], x)
    return x + h


class Control:
    """The prompt-to-prompt edit of one group ``[source, target...]`` as the
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


#: Most bytes of float32 probabilities computed at once at a site nobody edits.
PROBS_BYTES = 2 ** 28


def blocked_attention(q, k, v):
    """softmax(Q K^T / sqrt(d)) V over blocks of queries: the largest block
    that divides the pixels and keeps the block's probabilities, for the
    whole batch and all heads, within ``PROBS_BYTES``. One block where the
    site is small."""
    b, h, p, d = q.shape
    row_bytes = b * h * k.shape[2] * 4
    block = max(n for n in range(1, p + 1)
                if p % n == 0 and (n * row_bytes <= PROBS_BYTES or n == 1))

    def one(q_block):
        return jnp.einsum("bhqk,bhkd->bhqd", attention_probs(q_block, k), v)

    if block == p:
        return one(q)
    blocks = q.reshape(b, h, p // block, block, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, blocks)                       # (n, b, h, block, d)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, p, d)


def attention(p, x, context, heads, control, step, is_cross):
    src = context if is_cross else x
    q = split_heads(linear(p["to_q"], x), heads)
    k = split_heads(linear(p["to_k"], src), heads)
    v = split_heads(linear(p["to_v"], src), heads)
    edited = control is not None and (
        is_cross or q.shape[2] <= control.self_max_pixels)
    if edited:
        probs = control(attention_probs(q, k), step, is_cross)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    else:
        out = blocked_attention(q, k, v)
    return linear(p["to_out"], merge_heads(out))


def transformer(p, x, context, uc, control, step):
    """A site group (equation 4): as many blocks as the tree holds."""
    b, h, w, c = x.shape
    heads = c // uc["attention_head_size"]
    res = x
    x = group_norm(p["norm"], x, uc["norm_num_groups"], 1e-6).reshape(b, h * w, c)
    x = jnp.matmul(x, wide(p["proj_in"]["kernel"][0, 0])) + p["proj_in"]["bias"]
    for blk in p["blocks"]:
        x = x + attention(blk["attn1"], layer_norm(blk["ln1"], x), None, heads,
                          control, step, False)
        x = x + attention(blk["attn2"], layer_norm(blk["ln2"], x), context,
                          heads, control, step, True)
        hdn = linear(blk["ff_in"], layer_norm(blk["ln3"], x))
        val, gate = jnp.split(hdn, 2, axis=-1)
        x = x + linear(blk["ff_out"], val * gelu(gate))
    x = jnp.matmul(x, wide(p["proj_out"]["kernel"][0, 0])) + p["proj_out"]["bias"]
    return x.reshape(b, h, w, c) + res


def unet(w, cfg: dict, x, t, context, add, control=None, step=None):
    uc = cfg["unet"]
    g = uc["norm_num_groups"]
    depth = uc["transformer_depth"]
    t = jnp.broadcast_to(t, (x.shape[0],))
    temb = sinusoidal(t, uc["block_out_channels"][0])
    temb = linear(w["time_fc2"], silu(linear(w["time_fc1"], temb))) + add

    def tf(p, h, d):
        if len(p["blocks"]) != d:
            raise ValueError(f"a site group of {len(p['blocks'])} blocks where "
                             f"the configuration states {d}")
        return transformer(p, h, context, uc, control, step)

    h = conv(w["conv_in"], x)
    skips = [h]
    for lvl, block in enumerate(w["down"]):
        for i, rp in enumerate(block["resnets"]):
            h = res_block(rp, h, temb, g)
            if uc["attention_levels"][lvl] and depth[lvl]:
                h = tf(block["attns"][i], h, depth[lvl])
            skips.append(h)
        if "downsample" in block:
            h = conv(block["downsample"], h, stride=2, padding=((1, 1), (1, 1)))
            skips.append(h)
    h = res_block(w["mid"]["resnet1"], h, temb, g)
    h = tf(w["mid"]["attn"], h, depth[-1])
    h = res_block(w["mid"]["resnet2"], h, temb, g)
    for up, block in enumerate(w["up"]):
        lvl = len(w["up"]) - 1 - up
        for i, rp in enumerate(block["resnets"]):
            h = res_block(rp, jnp.concatenate([h, skips.pop()], axis=-1), temb, g)
            if uc["attention_levels"][lvl] and depth[lvl]:
                h = tf(block["attns"][i], h, depth[lvl])
        if "upsample" in block:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv(block["upsample"], h)
    return conv(w["conv_out"], silu(group_norm(w["norm_out"], h, g, 1e-5)))


# ---------------------------------------------------------------------------
# Autoencoder decoder
# ---------------------------------------------------------------------------


def decode(w, cfg: dict, latents):
    """One latent (1, h, w, c) to its image."""
    vc = cfg["vae"]
    g = vc["norm_num_groups"]
    p = w["decoder"]
    h = latents / vc["scaling_factor"]
    h = conv(p["post_quant_conv"], h)
    h = conv(p["conv_in"], h)
    h = res_block(p["mid"]["resnet1"], h, None, g)
    a = p["mid"]["attn"]
    b, hh, ww, c = h.shape
    y = group_norm(a["norm"], h, g, 1e-5).reshape(b, hh * ww, c)
    q, k, v = (linear(a[n_], y)[:, None] for n_ in "qkv")
    out = blocked_attention(q, k, v)[:, 0]
    h = h + linear(a["out"], out).reshape(b, hh, ww, c)
    h = res_block(p["mid"]["resnet2"], h, None, g)
    for block in p["up"]:
        for rp in block["resnets"]:
            h = res_block(rp, h, None, g)
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
    if sc["beta_schedule"] != "scaled_linear":
        raise ValueError(sc["beta_schedule"])
    betas = np.linspace(sc["beta_start"] ** 0.5, sc["beta_end"] ** 0.5, n,
                        dtype=np.float64) ** 2
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
    cross window of num_steps + 1 as in the paper's code). This member's
    cells run no phase gate, and the reference has none.
    """
    if edit.get("gate") is not None:
        raise ValueError("this reference samples every step in full")
    if cfg.get("prediction_type", "epsilon") != "epsilon":
        raise ValueError(cfg["prediction_type"])
    n = edit["num_steps"]
    guidance = edit["guidance_scale"]
    ts, a_t, a_prev = ddim_tables(cfg, n)
    b = 2

    def run(weights, x_T, ids, align):
        control = Control(edit["kind"],
                          int(edit["cross_replace_steps"] * (n + 1)), 0,
                          int(edit["self_replace_steps"] * n),
                          edit["self_max_pixels"], **align)
        wu = weights["unet"]
        context, pooled = conditioning(weights["text"], cfg, ids)
        add = added_embedding(wu, cfg, pooled)           # once, not a step
        x = jnp.broadcast_to(x_T, (b,) + x_T.shape[1:])

        def step_fn(x, inp):
            step, t, at, ap = inp
            out = unet(wu, cfg, jnp.concatenate([x, x]), t, context, add,
                       control, step)
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
