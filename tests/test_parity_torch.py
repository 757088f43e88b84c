"""Numerical parity vs torch — the real-checkpoint-path proof.

No SD weights exist in this environment, so parity is proven structurally:
random-init OUR params, export through the checkpoint name tables
(`p2p_tpu/models/checkpoint.py`), load them into the torch reference modules
(`transformers.CLIPTextModel` for the text tower; hand-built torch oracles of
diffusers' ResnetBlock2D / BasicTransformerBlock / GroupNorm for the U-Net
blocks), and compare forward outputs at f32 — this validates every layout
transform (linear transpose, conv OIHW↔HWIO) and op semantics (GN grouping,
GEGLU split order, quick_gelu, causal masking) on the exact path a real
checkpoint would take. Behavior spec: `/root/reference/main.py:29` loads the
diffusers pipeline these tables mirror.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from p2p_tpu.models import nn as jnn
from p2p_tpu.models.checkpoint import export_state_dict, text_encoder_entries
from p2p_tpu.models.config import TextEncoderConfig, UNetConfig
from p2p_tpu.models.text_encoder import apply_text_encoder, init_text_encoder
from p2p_tpu.models.unet import (
    _apply_resnet,
    _apply_transformer_block,
    _resnet_init,
    _transformer_block_init,
)


def _to_t(a):
    # np.array: writable copy (torch.from_numpy warns on jax's read-only views)
    return torch.from_numpy(np.array(a, dtype=np.float32))


# ---------------------------------------------------------------------------
# Text encoder vs transformers.CLIPTextModel
# ---------------------------------------------------------------------------


def test_text_encoder_matches_clip_text_model():
    cfg = TextEncoderConfig(vocab_size=120, hidden_dim=32, num_layers=2,
                            num_heads=2, max_length=16)
    params = init_text_encoder(jax.random.PRNGKey(7), cfg)
    sd = {k: _to_t(v) for k, v in
          export_state_dict(params, text_encoder_entries(cfg)).items()}

    hf_cfg = transformers.CLIPTextConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.hidden_dim * cfg.ff_mult,
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        max_position_embeddings=cfg.max_length, hidden_act="quick_gelu")
    model = transformers.CLIPTextModel(hf_cfg).eval()
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    # position_ids buffers may be "missing" from our export; nothing else.
    assert all("position_ids" in m for m in missing), missing

    rng = np.random.RandomState(0)
    ids = rng.randint(2, cfg.vocab_size, size=(3, cfg.max_length)).astype(np.int64)
    ids[:, 0] = 0
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).last_hidden_state.numpy()
    got = np.asarray(apply_text_encoder(params, cfg, jnp.asarray(ids, jnp.int32)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Hand-built torch oracles for the U-Net building blocks
# (diffusers ResnetBlock2D / BasicTransformerBlock semantics, written
# independently from their published architecture)
# ---------------------------------------------------------------------------


def _torch_linear(p):
    lin = torch.nn.Linear(p["kernel"].shape[0], p["kernel"].shape[1],
                          bias="bias" in p)
    with torch.no_grad():
        lin.weight.copy_(_to_t(p["kernel"]).T)
        if "bias" in p:
            lin.bias.copy_(_to_t(p["bias"]))
    return lin


def _torch_conv(p, stride=1, padding=1):
    kh, kw, ci, co = p["kernel"].shape
    conv = torch.nn.Conv2d(ci, co, (kh, kw), stride=stride, padding=padding)
    with torch.no_grad():
        conv.weight.copy_(_to_t(p["kernel"]).permute(3, 2, 0, 1))
        conv.bias.copy_(_to_t(p["bias"]))
    return conv


def _torch_groupnorm(p, groups, eps=1e-5):
    c = p["scale"].shape[0]
    gn = torch.nn.GroupNorm(min(groups, c), c, eps=eps)
    with torch.no_grad():
        gn.weight.copy_(_to_t(p["scale"]))
        gn.bias.copy_(_to_t(p["bias"]))
    return gn


def _torch_layernorm(p, eps=1e-5):
    ln = torch.nn.LayerNorm(p["scale"].shape[0], eps=eps)
    with torch.no_grad():
        ln.weight.copy_(_to_t(p["scale"]))
        ln.bias.copy_(_to_t(p["bias"]))
    return ln


def _torch_attention(p, x, context, heads, hook=None, is_cross=None):
    """diffusers CrossAttention forward (`/root/reference/ptp_utils.py:183-208`
    is the monkey-patched spec): q/k/v projections, head split, softmax(QKᵀ·s).
    ``hook(attn, is_cross)`` is the reference's controller detour, applied to
    the probability tensor before the V product (used by the e2e parity
    tests; None leaves the plain forward)."""
    q = _torch_linear(p["to_q"])(x)
    k = _torch_linear(p["to_k"])(context)
    v = _torch_linear(p["to_v"])(context)
    b, s_q, d = q.shape
    dh = d // heads

    def split(t):
        return t.reshape(b, -1, heads, dh).permute(0, 2, 1, 3)

    q, k, v = split(q), split(k), split(v)
    attn = torch.softmax(q @ k.transpose(-1, -2) * dh ** -0.5, dim=-1)
    if hook is not None:
        attn = hook(attn, is_cross)
    out = (attn @ v).permute(0, 2, 1, 3).reshape(b, s_q, d)
    return _torch_linear(p["to_out"])(out)


def test_groupnorm_matches_torch():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 6, 8).astype(np.float32)
    p = {"scale": rng.randn(8).astype(np.float32),
         "bias": rng.randn(8).astype(np.float32)}
    got = np.asarray(jnn.group_norm(p, jnp.asarray(x), groups=4))
    gn = _torch_groupnorm(p, 4)
    with torch.no_grad():
        want = gn(_to_t(x).permute(0, 3, 1, 2)).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_resnet_block_matches_torch_oracle():
    cfg = UNetConfig()
    rng = np.random.RandomState(2)
    in_ch, out_ch, temb_dim, groups = 16, 24, 32, 8
    p = _resnet_init(jax.random.PRNGKey(3), in_ch, out_ch, temb_dim)
    x = rng.randn(2, 8, 8, in_ch).astype(np.float32)
    temb = rng.randn(2, temb_dim).astype(np.float32)

    got = np.asarray(_apply_resnet(p, jnp.asarray(x), jnp.asarray(temb), groups))

    xt = _to_t(x).permute(0, 3, 1, 2)
    tt = _to_t(temb)
    with torch.no_grad():
        h = _torch_conv(p["conv1"])(torch.nn.functional.silu(
            _torch_groupnorm(p["norm1"], groups)(xt)))
        h = h + _torch_linear(p["time_proj"])(
            torch.nn.functional.silu(tt))[:, :, None, None]
        h = _torch_conv(p["conv2"])(torch.nn.functional.silu(
            _torch_groupnorm(p["norm2"], groups)(h)))
        skip = _torch_conv(p["skip"], padding=0)(xt)
        want = (skip + h).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_transformer_block_matches_torch_oracle():
    from p2p_tpu.controllers.base import AttnMeta
    from p2p_tpu.models.unet import _HookCtx
    from p2p_tpu.models.config import unet_layout, TINY_UNET

    dim, ctx_dim, heads = 32, 16, 4
    p = _transformer_block_init(jax.random.PRNGKey(4), dim, ctx_dim, ff_mult=2)
    rng = np.random.RandomState(5)
    x = rng.randn(2, 9, dim).astype(np.float32)
    context = rng.randn(2, 7, ctx_dim).astype(np.float32)

    # Layout stub: one self + one cross site, controller None.
    from p2p_tpu.controllers.base import AttnLayout, StoreConfig
    metas = (AttnMeta(0, "down", False, 3, heads, 9),
             AttnMeta(1, "down", True, 3, heads, 7))
    layout = AttnLayout(metas, StoreConfig())
    hook = _HookCtx(layout, None, (), jnp.int32(0), ("off",) * len(metas))
    got = np.asarray(_apply_transformer_block(p, jnp.asarray(x),
                                              jnp.asarray(context), heads, hook))

    with torch.no_grad():
        xt = _to_t(x)
        ct = _to_t(context)
        h1 = _torch_layernorm(p["ln1"])(xt)
        xt = xt + _torch_attention(p["attn1"], h1, h1, heads)
        xt = xt + _torch_attention(p["attn2"], _torch_layernorm(p["ln2"])(xt), ct, heads)
        h = _torch_linear(p["ff_in"])(_torch_layernorm(p["ln3"])(xt))
        val, gate = h.chunk(2, dim=-1)  # diffusers GEGLU split order
        xt = xt + _torch_linear(p["ff_out"])(
            val * torch.nn.functional.gelu(gate))
        want = xt.numpy()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_timestep_embedding_matches_torch_oracle():
    """diffusers `Timesteps(flip_sin_to_cos=True, downscale_freq_shift=0)`:
    [cos | sin] halves of t·exp(-ln(1e4)·i/half)."""
    import math

    t = np.array([0, 1, 500, 999], dtype=np.float32)
    dim = 32
    half = dim // 2
    with torch.no_grad():
        freqs = torch.exp(-math.log(10000.0) * torch.arange(half) / half)
        args = torch.from_numpy(t)[:, None] * freqs[None]
        want = torch.cat([torch.cos(args), torch.sin(args)], dim=-1).numpy()
    got = np.asarray(jnn.timestep_embedding(jnp.asarray(t), dim))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _torch_unet_forward(params, cfg, x, ctx, t_val):
    """Whole-model torch composition oracle: conv_in → down(resnet[+attn],
    skips, downsample) → mid → up(skip-concat, resnet[+attn], upsample) →
    out, with the sinusoidal→MLP time path — written against diffusers'
    UNet2DConditionModel wiring, independent of apply_unet's traversal.
    Catches wiring bugs (skip order, pad mode, upsample placement) that
    block-level oracles cannot. Returns the ε-prediction as NHWC numpy."""
    import math

    b = x.shape[0]
    with torch.no_grad():
        xt = _to_t(x).permute(0, 3, 1, 2)
        ct = _to_t(ctx)
        g = cfg.groups

        # Time path: [cos|sin] sinusoid → linear → silu → linear.
        half = cfg.block_channels[0] // 2
        freqs = torch.exp(-math.log(10000.0) * torch.arange(half) / half)
        args = torch.full((b, 1), float(t_val)) * freqs[None]
        sin_emb = torch.cat([torch.cos(args), torch.sin(args)], dim=-1)
        temb = _torch_linear(params["time_fc2"])(
            torch.nn.functional.silu(_torch_linear(params["time_fc1"])(sin_emb)))

        def resnet(p, h):
            r = _torch_conv(p["conv1"])(torch.nn.functional.silu(
                _torch_groupnorm(p["norm1"], g)(h)))
            r = r + _torch_linear(p["time_proj"])(
                torch.nn.functional.silu(temb))[:, :, None, None]
            r = _torch_conv(p["conv2"])(torch.nn.functional.silu(
                _torch_groupnorm(p["norm2"], g)(r)))
            skip = _torch_conv(p["skip"], padding=0)(h) if "skip" in p else h
            return skip + r

        def spatial_transformer(p, h, heads):
            bb, cc, hh, ww = h.shape
            res = h
            y = _torch_groupnorm(p["norm"], g, eps=1e-6)(h)
            y = y.permute(0, 2, 3, 1).reshape(bb, hh * ww, cc)
            y = _torch_linear({k: v[0, 0] if k == "kernel" else v
                               for k, v in p["proj_in"].items()})(y)
            for blk in p["blocks"]:
                h1 = _torch_layernorm(blk["ln1"])(y)
                y = y + _torch_attention(blk["attn1"], h1, h1, heads)
                y = y + _torch_attention(blk["attn2"],
                                         _torch_layernorm(blk["ln2"])(y), ct, heads)
                ff = _torch_linear(blk["ff_in"])(_torch_layernorm(blk["ln3"])(y))
                val, gate = ff.chunk(2, dim=-1)
                y = y + _torch_linear(blk["ff_out"])(
                    val * torch.nn.functional.gelu(gate))
            y = _torch_linear({k: v[0, 0] if k == "kernel" else v
                               for k, v in p["proj_out"].items()})(y)
            return y.reshape(bb, hh, ww, cc).permute(0, 3, 1, 2) + res

        h = _torch_conv(params["conv_in"])(xt)
        skips = [h]
        for level, block in enumerate(params["down"]):
            heads = cfg.heads_for(cfg.block_channels[level])
            for i, rp in enumerate(block["resnets"]):
                h = resnet(rp, h)
                if block["attns"]:
                    h = spatial_transformer(block["attns"][i], h, heads)
                skips.append(h)
            if "downsample" in block:
                h = _torch_conv(block["downsample"], stride=2, padding=1)(h)
                skips.append(h)

        mid_heads = cfg.heads_for(cfg.block_channels[-1])
        h = resnet(params["mid"]["resnet1"], h)
        h = spatial_transformer(params["mid"]["attn"], h, mid_heads)
        h = resnet(params["mid"]["resnet2"], h)

        for pos, block in enumerate(params["up"]):
            level = cfg.levels - 1 - pos
            heads = cfg.heads_for(cfg.block_channels[level])
            for i, rp in enumerate(block["resnets"]):
                h = torch.cat([h, skips.pop()], dim=1)
                h = resnet(rp, h)
                if block["attns"]:
                    h = spatial_transformer(block["attns"][i], h, heads)
            if "upsample" in block:
                h = torch.nn.functional.interpolate(h, scale_factor=2,
                                                    mode="nearest")
                h = _torch_conv(block["upsample"])(h)

        h = torch.nn.functional.silu(_torch_groupnorm(params["norm_out"], g)(h))
        return _torch_conv(params["conv_out"])(h).permute(0, 2, 3, 1).numpy()


def test_full_unet_matches_torch_oracle():
    from p2p_tpu.models.config import TINY_UNET, unet_layout
    from p2p_tpu.models.unet import apply_unet, init_unet

    cfg = TINY_UNET
    params = init_unet(jax.random.PRNGKey(21), cfg)
    layout = unet_layout(cfg)
    rng = np.random.RandomState(7)
    b = 2
    x = rng.randn(b, cfg.sample_size, cfg.sample_size,
                  cfg.in_channels).astype(np.float32)
    ctx = rng.randn(b, cfg.context_len, cfg.context_dim).astype(np.float32)
    t_val = 500

    got, _ = apply_unet(params, cfg, jnp.asarray(x), jnp.int32(t_val),
                        jnp.asarray(ctx), layout=layout)
    want = _torch_unet_forward(params, cfg, x, ctx, t_val)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5, rtol=1e-3)


def _torch_vae_roundtrip(params, cfg, image, got_lat):
    """Whole-VAE torch composition oracle (diffusers AutoencoderKL wiring):
    encoder with asymmetric (0,1)/(0,1) pre-pad before stride-2 downsamples
    and single-head mid attention, quant/post-quant convs, nearest-x2
    decoder. Returns (posterior-mean latent, decode of ``got_lat``)."""
    g = cfg.groups
    with torch.no_grad():
        def resnet(p, h):
            r = _torch_conv(p["conv1"])(torch.nn.functional.silu(
                _torch_groupnorm(p["norm1"], g)(h)))
            r = _torch_conv(p["conv2"])(torch.nn.functional.silu(
                _torch_groupnorm(p["norm2"], g)(r)))
            skip = _torch_conv(p["skip"], padding=0)(h) if "skip" in p else h
            return skip + r

        def mid_attn(p, h):
            bb, cc, hh, ww = h.shape
            y = _torch_groupnorm(p["norm"], g)(h)
            y = y.permute(0, 2, 3, 1).reshape(bb, hh * ww, cc)
            q = _torch_linear(p["q"])(y)
            k = _torch_linear(p["k"])(y)
            v = _torch_linear(p["v"])(y)
            attn = torch.softmax(q @ k.transpose(-1, -2) * cc ** -0.5, dim=-1)
            out = _torch_linear(p["out"])(attn @ v)
            return h + out.reshape(bb, hh, ww, cc).permute(0, 3, 1, 2)

        enc = params["encoder"]
        h = _torch_conv(enc["conv_in"])(_to_t(image).permute(0, 3, 1, 2))
        for block in enc["down"]:
            for rp in block["resnets"]:
                h = resnet(rp, h)
            if "downsample" in block:
                h = torch.nn.functional.pad(h, (0, 1, 0, 1))
                h = _torch_conv(block["downsample"], stride=2, padding=0)(h)
        h = resnet(enc["mid"]["resnet1"], h)
        h = mid_attn(enc["mid"]["attn"], h)
        h = resnet(enc["mid"]["resnet2"], h)
        h = _torch_conv(enc["conv_out"])(torch.nn.functional.silu(
            _torch_groupnorm(enc["norm_out"], g)(h)))
        moments = _torch_conv(enc["quant_conv"], padding=0)(h)
        mean = moments[:, :cfg.latent_channels]
        want_lat = (mean * cfg.scaling_factor).permute(0, 2, 3, 1).numpy()

        dec = params["decoder"]
        z = _to_t(got_lat).permute(0, 3, 1, 2) / cfg.scaling_factor
        h = _torch_conv(dec["post_quant_conv"], padding=0)(z)
        h = _torch_conv(dec["conv_in"])(h)
        h = resnet(dec["mid"]["resnet1"], h)
        h = mid_attn(dec["mid"]["attn"], h)
        h = resnet(dec["mid"]["resnet2"], h)
        for block in dec["up"]:
            for rp in block["resnets"]:
                h = resnet(rp, h)
            if "upsample" in block:
                h = torch.nn.functional.interpolate(h, scale_factor=2,
                                                    mode="nearest")
                h = _torch_conv(block["upsample"])(h)
        h = torch.nn.functional.silu(_torch_groupnorm(dec["norm_out"], g)(h))
        want_img = _torch_conv(dec["conv_out"])(h).permute(0, 2, 3, 1).numpy()
    return want_lat, want_img


def test_full_vae_matches_torch_oracle():
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.models.config import TINY_VAE

    cfg = TINY_VAE
    params = vae_mod.init_vae(jax.random.PRNGKey(31), cfg)
    rng = np.random.RandomState(9)
    image = rng.randn(2, 64, 64, cfg.in_channels).astype(np.float32) * 0.5

    got_lat = np.asarray(vae_mod.encode(params, cfg, jnp.asarray(image)))
    got_img = np.asarray(vae_mod.decode(params, cfg, jnp.asarray(got_lat)))
    want_lat, want_img = _torch_vae_roundtrip(params, cfg, image, got_lat)
    np.testing.assert_allclose(got_lat, want_lat, atol=3e-5, rtol=1e-3)
    np.testing.assert_allclose(got_img, want_img, atol=3e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# Full-scale SD-1.4 forwards vs the same oracles:
# every prior full-scale check was shapes-only (mapping-table round trips +
# eval_shape); these run ONE ε-prediction and ONE 512² VAE round trip at the
# real SD14 topology in f32, so a config transcription error inside the SD14
# U-Net (e.g. a wrong attn_levels/transformer_depth interaction) can no
# longer hide behind passing TINY-scale numerics. Ground truth being
# replaced: `StableDiffusionPipeline.from_pretrained` (/root/reference/main.py:29).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_full_unet_matches_torch_oracle_sd14_scale():
    from p2p_tpu.models.config import SD14_UNET, unet_layout
    from p2p_tpu.models.unet import apply_unet, init_unet

    cfg = SD14_UNET
    params = init_unet(jax.random.PRNGKey(22), cfg)
    layout = unet_layout(cfg)
    rng = np.random.RandomState(17)
    x = rng.randn(1, cfg.sample_size, cfg.sample_size,
                  cfg.in_channels).astype(np.float32)
    ctx = rng.randn(1, cfg.context_len, cfg.context_dim).astype(np.float32)
    t_val = 981  # first DDIM-50 timestep

    got, _ = apply_unet(params, cfg, jnp.asarray(x), jnp.int32(t_val),
                        jnp.asarray(ctx), layout=layout)
    want = _torch_unet_forward(params, cfg, x, ctx, t_val)
    # f32 end to end; the deeper 860M-param graph accumulates more rounding
    # than TINY, hence the slightly wider (still tight) tolerance.
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_full_vae_matches_torch_oracle_sd14_scale():
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.models.config import SD14_VAE

    cfg = SD14_VAE
    params = vae_mod.init_vae(jax.random.PRNGKey(32), cfg)
    rng = np.random.RandomState(19)
    image = rng.randn(1, 512, 512, cfg.in_channels).astype(np.float32) * 0.5

    got_lat = np.asarray(vae_mod.encode(params, cfg, jnp.asarray(image)))
    got_img = np.asarray(vae_mod.decode(params, cfg, jnp.asarray(got_lat)))
    assert got_lat.shape == (1, 64, 64, cfg.latent_channels)
    assert got_img.shape == (1, 512, 512, cfg.in_channels)
    want_lat, want_img = _torch_vae_roundtrip(params, cfg, image, got_lat)
    np.testing.assert_allclose(got_lat, want_lat, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(got_img, want_img, atol=2e-4, rtol=1e-3)
