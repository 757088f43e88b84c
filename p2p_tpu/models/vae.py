"""KL autoencoder (image ⇄ latent codecs), diffusers `AutoencoderKL` topology.

The reference touches the VAE at three points, which are the API here:
encode to the posterior **mean** scaled by 0.18215
(`/root/reference/null_text.py:519-531` — it uses ``latent_dist.mean``, not a
sample, for inversion), decode with the inverse scale
(`/root/reference/ptp_utils.py:79-85`), and the uint8 image conversion
``(x/2+.5).clamp(0,1)·255``. All NHWC.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..obs import launches
from .config import VAEConfig
from . import nn

Params = Dict[str, Any]


def _resnet_init(key, in_ch, out_ch, kd):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "norm1": nn.norm_init(in_ch),
        "conv1": nn.conv_init(k1, in_ch, out_ch, kernel_dtype=kd),
        "norm2": nn.norm_init(out_ch),
        "conv2": nn.conv_init(k2, out_ch, out_ch, kernel_dtype=kd),
    }
    if in_ch != out_ch:
        p["skip"] = nn.conv_init(k3, in_ch, out_ch, kernel=1, kernel_dtype=kd)
    return p


def _apply_resnet(p, x, groups):
    h = nn.conv2d(p["conv1"], nn.silu(nn.group_norm(p["norm1"], x, groups)))
    h = nn.conv2d(p["conv2"], nn.silu(nn.group_norm(p["norm2"], h, groups)))
    if "skip" in p:
        x = nn.conv2d(p["skip"], x)
    return x + h


def _attn_init(key, ch, kd):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "norm": nn.norm_init(ch),
        "q": nn.linear_init(k1, ch, ch, kernel_dtype=kd),
        "k": nn.linear_init(k2, ch, ch, kernel_dtype=kd),
        "v": nn.linear_init(k3, ch, ch, kernel_dtype=kd),
        "out": nn.linear_init(k4, ch, ch, kernel_dtype=kd),
    }


def _apply_attn(p, x, groups):
    """Single-head full self-attention over pixels (VAE mid block)."""
    b, h, w, c = x.shape
    residual = x
    y = nn.group_norm(p["norm"], x, groups).reshape(b, h * w, c)
    q = nn.linear(p["q"], y)[:, None]
    k = nn.linear(p["k"], y)[:, None]
    v = nn.linear(p["v"], y)[:, None]
    out = nn.fused_attention(q, k, v, c ** -0.5)[:, 0]
    out = nn.linear(p["out"], out).reshape(b, h, w, c)
    return residual + out


def init_vae(key: jax.Array, cfg: VAEConfig) -> Params:
    keys = iter(jax.random.split(key, 64))
    chs = [cfg.base_channels * m for m in cfg.channel_mults]
    top = chs[-1]
    lat = cfg.latent_channels
    kd = cfg.kernel_dtype
    conv = partial(nn.conv_init, kernel_dtype=kd)

    enc: Params = {"conv_in": conv(next(keys), cfg.in_channels, chs[0]),
                   "down": []}
    in_ch = chs[0]
    for level, out_ch in enumerate(chs):
        block = {"resnets": []}
        for _ in range(cfg.layers_per_block):
            block["resnets"].append(_resnet_init(next(keys), in_ch, out_ch, kd))
            in_ch = out_ch
        if level != len(chs) - 1:
            block["downsample"] = conv(next(keys), out_ch, out_ch)
        enc["down"].append(block)
    enc["mid"] = {
        "resnet1": _resnet_init(next(keys), top, top, kd),
        "attn": _attn_init(next(keys), top, kd),
        "resnet2": _resnet_init(next(keys), top, top, kd),
    }
    enc["norm_out"] = nn.norm_init(top)
    if cfg.kind == "vq":
        # VQ encoder emits the embedding directly; KL emits mean ‖ logvar.
        enc["conv_out"] = conv(next(keys), top, lat)
        enc["quant_conv"] = conv(next(keys), lat, lat, kernel=1)
    else:
        enc["conv_out"] = conv(next(keys), top, 2 * lat)
        enc["quant_conv"] = conv(next(keys), 2 * lat, 2 * lat, kernel=1)

    dec: Params = {
        "post_quant_conv": conv(next(keys), lat, lat, kernel=1),
        "conv_in": conv(next(keys), lat, top),
        "mid": {
            "resnet1": _resnet_init(next(keys), top, top, kd),
            "attn": _attn_init(next(keys), top, kd),
            "resnet2": _resnet_init(next(keys), top, top, kd),
        },
        "up": [],
    }
    in_ch = top
    for level in reversed(range(len(chs))):
        out_ch = chs[level]
        block = {"resnets": []}
        for _ in range(cfg.layers_per_block + 1):
            block["resnets"].append(_resnet_init(next(keys), in_ch, out_ch, kd))
            in_ch = out_ch
        if level != 0:
            block["upsample"] = conv(next(keys), out_ch, out_ch)
        dec["up"].append(block)
    dec["norm_out"] = nn.norm_init(chs[0])
    dec["conv_out"] = conv(next(keys), chs[0], cfg.in_channels)

    params = {"encoder": enc, "decoder": dec}
    if cfg.kind == "vq":
        params["codebook"] = (jax.random.uniform(
            next(keys), (cfg.num_codebook, lat), jnp.float32,
            -1.0 / cfg.num_codebook, 1.0 / cfg.num_codebook))
    return params


def _encoder_trunk(params: Params, cfg: VAEConfig, image: jax.Array) -> jax.Array:
    """Shared encoder body through quant_conv: conv_in → down blocks (with
    diffusers' asymmetric (0,1)/(0,1) pad before each stride-2 conv) → mid →
    norm/conv_out → quant_conv. KL and VQ differ only in what the output
    means (mean‖logvar vs embedding)."""
    p = params["encoder"]
    g = cfg.groups
    h = nn.conv2d(p["conv_in"], image)
    for block in p["down"]:
        for resnet in block["resnets"]:
            h = _apply_resnet(resnet, h, g)
        if "downsample" in block:
            h = jnp.pad(h, ((0, 0), (0, 1), (0, 1), (0, 0)))
            h = nn.conv2d(block["downsample"], h, stride=2, padding="VALID")
    h = _apply_resnet(p["mid"]["resnet1"], h, g)
    h = _apply_attn(p["mid"]["attn"], h, g)
    h = _apply_resnet(p["mid"]["resnet2"], h, g)
    h = nn.conv2d(p["conv_out"], nn.silu(nn.group_norm(p["norm_out"], h, g)))
    return nn.conv2d(p["quant_conv"], h)


def encode_moments(params: Params, cfg: VAEConfig, image: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """image (B,H,W,3) in [-1,1] → posterior (mean, logvar), each
    (B, H/8, W/8, latent_channels) for the SD VAE's 3 downsamples."""
    moments = _encoder_trunk(params, cfg, image)
    mean, logvar = jnp.split(moments, 2, axis=-1)
    return mean, jnp.clip(logvar, -30.0, 20.0)


def encode(params: Params, cfg: VAEConfig, image: jax.Array) -> jax.Array:
    """Deterministic latent: scaled posterior mean
    (`/root/reference/null_text.py:527` uses ``.mean * 0.18215``).
    For VQ the encoder output is the (pre-quantization) embedding."""
    if cfg.kind == "vq":
        return _encoder_trunk(params, cfg, image) * cfg.scaling_factor
    mean, _ = encode_moments(params, cfg, image)
    return mean * cfg.scaling_factor


def quantize(params: Params, cfg: VAEConfig, z: jax.Array) -> jax.Array:
    """Snap each latent vector to its nearest codebook entry (L2) — the VQ
    lookup diffusers' ``VQModel.decode`` performs before decoding. Distances
    expand to z·z − 2 z·e + e·e so the hot op is one (pixels, lat)×(lat, K)
    matmul; the argmin gather is trivially small."""
    cb = params["codebook"].astype(jnp.float32)           # (K, C)
    zf = z.astype(jnp.float32)
    flat = zf.reshape(-1, zf.shape[-1])                   # (P, C)
    d = (jnp.sum(flat * flat, axis=1, keepdims=True)
         - 2.0 * flat @ cb.T
         + jnp.sum(cb * cb, axis=1)[None])
    idx = jnp.argmin(d, axis=1)
    return cb[idx].reshape(z.shape).astype(z.dtype)


def decode(params: Params, cfg: VAEConfig, latents: jax.Array) -> jax.Array:
    """latents (B,h,w,4) → image (B,H,W,3) in [-1,1]
    (`/root/reference/ptp_utils.py:79-84`: input scaled by 1/0.18215 — the
    reference routes BOTH the SD KL-VAE and the LDM VQ decode through this
    same function, `/root/reference/ptp_utils.py:124`).

    The batch is decoded in ``decode_chunks`` equal chunks, one after the
    other in the same program (an unrolled loop: nothing here is a ``while``),
    so that the widest activation alive is a chunk's and not the batch's."""
    n = decode_chunks(cfg, latents.shape)
    launches.note_decode_chunks(n)
    if n == 1:      # the program of every batch that fits is the one it was
        return _decode(params, cfg, latents)
    return jnp.concatenate([_decode(params, cfg, chunk)
                            for chunk in jnp.split(latents, n, axis=0)], axis=0)


#: The widest activation of one decode chunk (float32, at the image's size
#: and ``base_channels`` wide) stays under this many bytes: the two images of
#: an edit at 768² (2 x 768² x 128 x 4 B = 604 MB) are one chunk as they
#: always were, at 1024² (1.07 GB) they are two.
DECODE_CHUNK_BYTES = 640 * 10**6


def decode_chunks(cfg: VAEConfig, latent_shape) -> int:
    """How many chunks ``decode`` takes a batch of ``latent_shape`` (B, h, w,
    c) in: the fewest equal ones whose widest activation is at most
    ``DECODE_CHUNK_BYTES``, a single image where even that is more."""
    b, h, w, _ = latent_shape
    up = 2 ** (len(cfg.channel_mults) - 1)
    image_bytes = 4 * h * up * w * up * cfg.base_channels
    return next(n for n in range(1, b + 1)
                if b % n == 0 and (b // n * image_bytes <= DECODE_CHUNK_BYTES
                                   or n == b))


def _decode(params: Params, cfg: VAEConfig, latents: jax.Array) -> jax.Array:
    p = params["decoder"]
    g = cfg.groups
    # Scopes: ``vae.decode/{conv_in,mid,up<n>,conv_out}``, n in the order the
    # blocks run (docs/OBSERVABILITY.md, "Scope vocabulary").
    with jax.named_scope("vae.decode"):
        with jax.named_scope("conv_in"):
            h = latents / cfg.scaling_factor
            if cfg.kind == "vq":
                h = quantize(params, cfg, h)
            h = nn.conv2d(p["post_quant_conv"], h)
            h = nn.conv2d(p["conv_in"], h)
        with jax.named_scope("mid"):
            h = _apply_resnet(p["mid"]["resnet1"], h, g)
            h = _apply_attn(p["mid"]["attn"], h, g)
            h = _apply_resnet(p["mid"]["resnet2"], h, g)
        for n, block in enumerate(p["up"]):
            with jax.named_scope(f"up{n}"):
                for resnet in block["resnets"]:
                    h = _apply_resnet(resnet, h, g)
                if "upsample" in block:
                    h = nn.conv2d(block["upsample"], nn.upsample_nearest_2x(h))
        with jax.named_scope("conv_out"):
            return nn.conv2d(p["conv_out"],
                             nn.silu(nn.group_norm(p["norm_out"], h, g)))


def to_uint8(image: jax.Array) -> jax.Array:
    """[-1,1] float → uint8 HWC (`/root/reference/ptp_utils.py:82-84`).
    Scoped with the decoder's last convolution, which it follows."""
    with jax.named_scope("vae.decode/conv_out"):
        return (jnp.clip(image / 2 + 0.5, 0.0, 1.0) * 255).astype(jnp.uint8)
