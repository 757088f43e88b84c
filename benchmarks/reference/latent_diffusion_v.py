"""Plain reference of a latent-diffusion prompt-to-prompt edit whose U-Net
predicts v (SD-2.1 at 768 x 768).

Written from the papers, in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision, with no kernels, no cache and no batching over
edit groups. It imports nothing of the program and nothing of the other
reference (``latent_diffusion.py``, which it repeats where the mathematics is
the same; a later benchmark PR may fold the two). It reads the sizes from the
configuration's JSON file and the weights from the tree the benchmark made
from ``--seed`` (``lib/weights.py``); token ids, the alignment of the two
prompts and the starting noise it works out itself.

Sources, by part:

- U-Net, text tower, autoencoder: Rombach et al., "High-Resolution Image
  Synthesis with Latent Diffusion Models" (CVPR 2022) and the published
  ``unet/config.json``, ``text_encoder/config.json``, ``vae/config.json`` of
  ``stabilityai/stable-diffusion-2-1``: heads of a fixed width
  (``attention_head_dim`` 5/10/20/20 heads of 64), ``proj_in`` / ``proj_out``
  as linear maps, OpenCLIP ViT-H/14's text tower as diffusers ships it (23
  layers, causal, GELU, pre-LN, final LN).
- v-parameterisation: Salimans & Ho, "Progressive Distillation for Fast
  Sampling of Diffusion Models" (ICLR 2022), section 4 and appendix D: with
  alpha = sqrt(a_t), sigma = sqrt(1 - a_t) and x_t = alpha x_0 + sigma eps the
  network predicts v = alpha eps - sigma x_0, so eps = alpha v + sigma x_t and
  x_0 = alpha x_t - sigma v. ``prediction_type`` is a top-level key of the
  configuration (``scheduler/scheduler_config.json``: ``v_prediction``);
  ``epsilon`` there makes this file the eps-reference.
- Sampler: Song et al., "Denoising Diffusion Implicit Models" (ICLR 2021),
  eq. 12 with sigma = 0; classifier-free guidance: Ho & Salimans 2022, on the
  network's own output: v = v_u + g (v_c - v_u). The conversion to eps is
  affine in v with the same x_t on both branches, so guiding v and then
  converting equals converting and then guiding eps.
- Attention control: Hertz et al., "Prompt-to-Prompt Image Editing with Cross
  Attention Control" (2022), section 3: word swap (replace), adding a phrase
  (refine), the cross window tau_c and the self-attention injection window.
- Phase gate (a documented approximation of the program, README "phase-gated
  sampling"): past the gate step only the conditional branch runs, every
  cross-attention output is the one the last full step produced, and guidance
  is out_c + (g - 1) r with r = out_c - out_u of the last full step, **in the
  network's output space** (v here): the space guidance is defined in, so the
  gated step is the full step's formula with the unconditional branch frozen.
  (Held in eps-space the same r would weigh alpha_gate / alpha_t more at step
  t, since eps_c - eps_u = alpha_t (v_c - v_u); the two are different
  approximations, and the program's is this one.)

Departures from the published description, all inherited from the
configuration as the program runs it: the hash word tokenizer stands in for
BPE (one token per word of at most 8 letters); GroupNorm epsilon is 1e-5 in
the autoencoder as in the U-Net's residual blocks (published 1e-6; the
variances are of order 1); ``upcast_attention`` changes nothing in float32;
DDIM with ``steps_offset`` 0 where the checkpoint's scheduler has 1.

Departure of this file from "no blocking": the probabilities of a large
self-attention site do not fit (5 heads x 9216^2 x 4 B = 1.7 GB for one row
of the batch at the 96 x 96 sites, 849 MB a site at 48 x 48 for the batch of
4), so wherever they would take more than ``PROBS_BYTES`` the site is
computed over blocks of queries (``blocked_attention``). Softmax runs along
the keys, so each query's row is whole inside its block: the same arithmetic
in the same order, only never all rows at once. The edited sites (all cross
sites, self sites up to ``self_max_pixels``) are small and stay whole. The
autoencoder's one attention (9216 keys, one head of 512) is blocked the same
way.
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


def token_ids(cfg: dict, prompt: str) -> np.ndarray:
    """Hash word tokenizer: BOS, one id per word, EOS, padded with EOS."""
    vocab = cfg["text_encoder"]["vocab_size"]
    length = cfg["text_encoder"]["max_position_embeddings"]
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
    return np.eye(cfg["text_encoder"]["max_position_embeddings"],
                  dtype=np.float32)


def refine_alignment(cfg: dict, source: str, target: str):
    """Adding a phrase: for every target token the source token it came
    from, and 1 where there is one (P2P section 3.2, "adding a new phrase").
    The target holds the source's words in order with words added."""
    length = cfg["text_encoder"]["max_position_embeddings"]
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


def linear(p, x):
    y = jnp.matmul(x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def conv(p, x, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), padding,
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
    return softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k)
                   * scale)


# ---------------------------------------------------------------------------
# Text tower
# ---------------------------------------------------------------------------


def text_encoder(w, cfg: dict, ids):
    tc = cfg["text_encoder"]
    n = ids.shape[1]
    x = w["token_embed"][ids] + w["pos_embed"][:n]
    mask = 0.0
    if tc["causal"]:
        mask = jnp.where(jnp.arange(n)[None, :] > jnp.arange(n)[:, None],
                         -jnp.inf, 0.0)
    act = (lambda v: v * jax.nn.sigmoid(1.702 * v)) \
        if tc["hidden_act"] == "quick_gelu" else gelu
    heads = tc["num_attention_heads"]
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
    return layer_norm(w["final_ln"], x)


# ---------------------------------------------------------------------------
# U-Net
# ---------------------------------------------------------------------------


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


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


def transformer(p, x, context, uc, control, step, cross_cache):
    """Spatial transformer. ``cross_cache`` is None, or a dict that says what
    to do with the cross-attention outputs: ``{"store": []}`` appends the
    conditional half's, ``{"use": iterator}`` takes them from there."""
    b, h, w, c = x.shape
    heads = c // uc["attention_head_size"] if uc.get("attention_head_size") \
        else uc["num_attention_heads"]
    res = x
    x = group_norm(p["norm"], x, uc["norm_num_groups"], 1e-6).reshape(b, h * w, c)
    x = jnp.matmul(x, p["proj_in"]["kernel"][0, 0]) \
        + p["proj_in"]["bias"]
    for blk in p["blocks"]:
        x = x + attention(blk["attn1"], layer_norm(blk["ln1"], x), None, heads,
                          control, step, False)
        if cross_cache is not None and "use" in cross_cache:
            x = x + next(cross_cache["use"])
        else:
            a = attention(blk["attn2"], layer_norm(blk["ln2"], x), context,
                          heads, control, step, True)
            if cross_cache is not None:
                cross_cache["store"].append(a[a.shape[0] // 2:])
            x = x + a
        hdn = linear(blk["ff_in"], layer_norm(blk["ln3"], x))
        val, gate = jnp.split(hdn, 2, axis=-1)
        x = x + linear(blk["ff_out"], val * gelu(gate))
    x = jnp.matmul(x, p["proj_out"]["kernel"][0, 0]) \
        + p["proj_out"]["bias"]
    return x.reshape(b, h, w, c) + res


def unet(w, cfg: dict, x, t, context, control=None, step=None,
         cross_cache=None):
    uc = cfg["unet"]
    g = uc["norm_num_groups"]
    t = jnp.broadcast_to(t, (x.shape[0],))
    temb = timestep_embedding(t, uc["block_out_channels"][0])
    temb = linear(w["time_fc2"], silu(linear(w["time_fc1"], temb)))

    def tf(p, h):
        return transformer(p, h, context, uc, control, step, cross_cache)

    h = conv(w["conv_in"], x)
    skips = [h]
    for block in w["down"]:
        for i, rp in enumerate(block["resnets"]):
            h = res_block(rp, h, temb, g)
            if block["attns"]:
                h = tf(block["attns"][i], h)
            skips.append(h)
        if "downsample" in block:
            h = conv(block["downsample"], h, stride=2, padding=((1, 1), (1, 1)))
            skips.append(h)
    h = res_block(w["mid"]["resnet1"], h, temb, g)
    h = tf(w["mid"]["attn"], h)
    h = res_block(w["mid"]["resnet2"], h, temb, g)
    for block in w["up"]:
        for i, rp in enumerate(block["resnets"]):
            h = res_block(rp, jnp.concatenate([h, skips.pop()], axis=-1), temb, g)
            if block["attns"]:
                h = tf(block["attns"][i], h)
        if "upsample" in block:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv(block["upsample"], h)
    return conv(w["conv_out"], silu(group_norm(w["norm_out"], h, g, 1e-5)))


# ---------------------------------------------------------------------------
# Autoencoder decoder
# ---------------------------------------------------------------------------


def decode(w, cfg: dict, latents):
    vc = cfg["vae"]
    g = vc["norm_num_groups"]
    p = w["decoder"]
    h = latents / vc["scaling_factor"]
    if vc["kind"] == "vq":
        cb = w["codebook"]
        flat = h.reshape(-1, h.shape[-1])
        d = ((flat ** 2).sum(1, keepdims=True)
             - 2.0 * jnp.matmul(flat, cb.T)
             + (cb ** 2).sum(1)[None])
        h = cb[jnp.argmin(d, axis=1)].reshape(h.shape)
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


def ddim_update(cfg: dict, x, out, a_t, a_prev):
    """x_t -> x_prev from the network's (guided) output: eps, or v with
    eps = alpha v + sigma x_t and x_0 = alpha x_t - sigma v."""
    alpha, sigma = jnp.sqrt(a_t), jnp.sqrt(1.0 - a_t)
    kind = cfg.get("prediction_type", "epsilon")
    if kind == "v_prediction":
        eps, x0 = alpha * out + sigma * x, alpha * x - sigma * out
    elif kind == "epsilon":
        eps, x0 = out, (x - sigma * out) / alpha
    else:
        raise ValueError(kind)
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
    self_replace_steps, self_max_pixels, and optionally gate (fractions of
    the step count, the cross window of num_steps + 1 as in the paper's
    code).
    """
    n = edit["num_steps"]
    guidance = edit["guidance_scale"]
    gate = edit.get("gate")
    gate_step = n if gate is None else int(round(gate * n))
    ts, a_t, a_prev = ddim_tables(cfg, n)
    b = 2

    def run(weights, x_T, ids, align):
        control = Control(edit["kind"],
                          int(edit["cross_replace_steps"] * (n + 1)), 0,
                          int(edit["self_replace_steps"] * n),
                          edit["self_max_pixels"], **align)
        wu = weights["unet"]
        context = text_encoder(weights["text"], cfg, ids)
        x = jnp.broadcast_to(x_T, (b,) + x_T.shape[1:])

        def full_step(x, inp, cache=None):
            step, t, at, ap = inp
            out = unet(wu, cfg, jnp.concatenate([x, x]), t, context, control,
                       step, cache)
            resid = out[b:] - out[:b]           # in the network's output space
            x = ddim_update(cfg, x, out[:b] + guidance * resid, at, ap)
            return x, resid

        steps = jnp.arange(n, dtype=jnp.int32)
        sched = (steps, ts, a_t, a_prev)
        last_full = gate_step - (1 if gate_step < n else 0)
        x, _ = jax.lax.scan(lambda x, inp: (full_step(x, inp)[0], None), x,
                            tuple(s[:last_full] for s in sched))
        if gate_step < n:
            cache = {"store": []}
            x, resid = full_step(x, tuple(s[last_full] for s in sched), cache)
            kept = tuple(cache["store"])

            def cond_step(x, inp):
                _, t, at, ap = inp
                out_c = unet(wu, cfg, x, t, context[b:], None, None,
                             {"use": iter(kept)})
                x = ddim_update(cfg, x, out_c + (guidance - 1.0) * resid, at, ap)
                return x, None

            x, _ = jax.lax.scan(cond_step, x,
                                tuple(s[gate_step:] for s in sched))
        image = decode(weights["vae"], cfg, x)
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
