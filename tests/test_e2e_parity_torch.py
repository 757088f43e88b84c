"""End-to-end sampling-loop parity vs hand-rolled torch reference pipelines.

The module-level oracles (tests/test_parity_torch.py) prove each block; these
ten tests prove the *composition* the north star calls "pixel-matching the
PyTorch reference": tokenize → text encode → CFG batch-doubling → per-layer
attention hook → scheduler update → (LocalBlend/SpatialReplace latent hook) →
VAE decode → uint8, run once through our jitted `text2image` and once through
an independent torch loop written against the reference's semantics. Covered
end to end: Replace / Refine / chained Reweight, ε- and v-prediction, DDIM
and PLMS, the LDM VQ backend, LocalBlend, SpatialReplace + negative prompt,
the null-text replay path (per-step uncond embeddings), and null-text
inversion itself (torch.optim.Adam vs our closed-form while_loop). Shared
ingredients:

- loop structure and CFG combine: `/root/reference/ptp_utils.py:65-76,129-172`
- controller math: `/root/reference/main.py:85-98,162-230` (cond-half-only
  edits, cross alpha-schedule blend, self-injection window)
- edit precompute: the reference's OWN `seq_aligner.get_replacement_mapper`
  and `ptp_utils.get_time_words_attention_alpha` (imported from
  /root/reference, torch CPU) with the same tokenizer on both sides
- DDIM update: closed form of `/root/reference/null_text.py:471-480` with
  set_alpha_to_one=False semantics
- decode: `/root/reference/ptp_utils.py:79-85`

Weights are shared: random-init OUR params, consumed directly by the torch
oracle modules (and through `export_state_dict` for the CLIP text tower).
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

# End-to-end torch-pipeline parity is the suite's most expensive family
# (~10 s per case warm, minutes cold): slow lane.
pytestmark = pytest.mark.slow

from p2p_tpu.controllers import factory
from p2p_tpu.engine.sampler import Pipeline, text2image
from p2p_tpu.models import TINY, init_text_encoder, init_unet
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models.checkpoint import export_state_dict, text_encoder_entries
from p2p_tpu.ops import schedulers as sched_mod
from p2p_tpu.utils.tokenizer import HashWordTokenizer, pad_ids

from test_parity_torch import (
    _to_t,
    _torch_attention,
    _torch_conv,
    _torch_groupnorm,
    _torch_layernorm,
    _torch_linear,
)

REFERENCE_DIR = "/root/reference"

NUM_STEPS = 3
GUIDANCE = 7.5
CROSS_REPLACE = 0.8
SELF_REPLACE = 0.5
SELF_MAX_PIXELS = 16 * 16

# One prompt pair per edit kind: same word count for Replace/Reweight, a word
# insertion for Refine (NW-aligned gather path). "replace_vpred" reruns the
# Replace edit on a v-prediction backend (the SD-2.1 768-v convention the
# reference marks "Not work", `/root/reference/main.py:27`) — the torch loop
# then converts v → ε with the independent closed form ε = √ᾱ·v + √(1−ᾱ)·x.
PROMPTS_BY_MODE = {
    "replace": ["a cat riding a bike", "a dog riding a bike"],
    "refine": ["a cat riding a bike", "a fluffy cat riding a bike"],
    "reweight_on_replace": ["a cat riding a bike", "a dog riding a bike"],
    "replace_vpred": ["a cat riding a bike", "a dog riding a bike"],
}


def _reference_modules():
    if not os.path.isdir(REFERENCE_DIR):
        pytest.skip("reference checkout not available")
    sys.path.insert(0, REFERENCE_DIR)
    try:
        import ptp_utils as ref_ptp
        import seq_aligner as ref_aligner
    except Exception as e:  # pragma: no cover
        pytest.skip(f"reference import failed: {e}")
    finally:
        sys.path.remove(REFERENCE_DIR)
    return ref_ptp, ref_aligner


def _torch_vae_resnet(p, h, g):
    """VAE resnet oracle (no time embedding), shared by encode/decode."""
    r = _torch_conv(p["conv1"])(torch.nn.functional.silu(
        _torch_groupnorm(p["norm1"], g)(h)))
    r = _torch_conv(p["conv2"])(torch.nn.functional.silu(
        _torch_groupnorm(p["norm2"], g)(r)))
    skip = _torch_conv(p["skip"], padding=0)(h) if "skip" in p else h
    return skip + r


def _torch_vae_mid_attn(p, h, g):
    """VAE mid-block single-head full self-attention oracle."""
    bb, cc, hh, ww = h.shape
    y = _torch_groupnorm(p["norm"], g)(h)
    y = y.permute(0, 2, 3, 1).reshape(bb, hh * ww, cc)
    q = _torch_linear(p["q"])(y)
    k = _torch_linear(p["k"])(y)
    v = _torch_linear(p["v"])(y)
    attn = torch.softmax(q @ k.transpose(-1, -2) * cc ** -0.5, dim=-1)
    out = _torch_linear(p["out"])(attn @ v)
    return h + out.reshape(bb, hh, ww, cc).permute(0, 3, 1, 2)


def _torch_unet(params, cfg, xt, t_val, ct, hook):
    """Full U-Net composition oracle (same wiring as
    tests/test_parity_torch.py::test_full_unet_matches_torch_oracle) with the
    attention hook threaded through every site in call order."""
    import math

    b = xt.shape[0]
    g = cfg.groups

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
            y = y + _torch_attention(blk["attn1"], h1, h1, heads,
                                     hook=hook, is_cross=False)
            y = y + _torch_attention(blk["attn2"],
                                     _torch_layernorm(blk["ln2"])(y), ct, heads,
                                     hook=hook, is_cross=True)
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
    return _torch_conv(params["conv_out"])(h)


def _torch_vae_decode(params, cfg, z):
    """Decoder half of the VAE composition oracle
    (tests/test_parity_torch.py::test_full_vae_matches_torch_oracle).
    Mirrors `vae.decode`'s structure: unscale, VQ codebook snap when
    ``cfg.kind == 'vq'`` (`/root/reference/ptp_utils.py:124` routes the LDM
    VQ decode through the same `latent2image`), then the decoder trunk."""
    g = cfg.groups
    dec = params["decoder"]
    h = z / cfg.scaling_factor
    if cfg.kind == "vq":
        h = _torch_vq_quantize(params, h)
    h = _torch_conv(dec["post_quant_conv"], padding=0)(h)
    h = _torch_conv(dec["conv_in"])(h)
    h = _torch_vae_resnet(dec["mid"]["resnet1"], h, g)
    h = _torch_vae_mid_attn(dec["mid"]["attn"], h, g)
    h = _torch_vae_resnet(dec["mid"]["resnet2"], h, g)
    for block in dec["up"]:
        for rp in block["resnets"]:
            h = _torch_vae_resnet(rp, h, g)
        if "upsample" in block:
            h = torch.nn.functional.interpolate(h, scale_factor=2,
                                                mode="nearest")
            h = _torch_conv(block["upsample"])(h)
    h = torch.nn.functional.silu(_torch_groupnorm(dec["norm_out"], g)(h))
    return _torch_conv(dec["conv_out"])(h)


def _torch_vae_encode(params, cfg, image):
    """Encoder half of the VAE composition oracle: posterior mean × scale
    (`/root/reference/null_text.py:519-531` uses ``latent_dist.mean``)."""
    g = cfg.groups
    enc = params["encoder"]
    h = _torch_conv(enc["conv_in"])(image)
    for block in enc["down"]:
        for rp in block["resnets"]:
            h = _torch_vae_resnet(rp, h, g)
        if "downsample" in block:
            h = torch.nn.functional.pad(h, (0, 1, 0, 1))
            h = _torch_conv(block["downsample"], stride=2, padding=0)(h)
    h = _torch_vae_resnet(enc["mid"]["resnet1"], h, g)
    h = _torch_vae_mid_attn(enc["mid"]["attn"], h, g)
    h = _torch_vae_resnet(enc["mid"]["resnet2"], h, g)
    h = _torch_conv(enc["conv_out"])(torch.nn.functional.silu(
        _torch_groupnorm(enc["norm_out"], g)(h)))
    moments = _torch_conv(enc["quant_conv"], padding=0)(h)
    return moments[:, :cfg.latent_channels] * cfg.scaling_factor


def _torch_text_encode(cfg, text_params, tok, prompts):
    """CLIP text tower on exported weights (guarded load), returning
    last_hidden_state rows for ``prompts``."""
    hf_cfg = transformers.CLIPTextConfig(
        vocab_size=cfg.text.vocab_size, hidden_size=cfg.text.hidden_dim,
        intermediate_size=cfg.text.hidden_dim * cfg.text.ff_mult,
        num_hidden_layers=cfg.text.num_layers,
        num_attention_heads=cfg.text.num_heads,
        max_position_embeddings=cfg.text.max_length, hidden_act="quick_gelu")
    text_model = transformers.CLIPTextModel(hf_cfg).eval()
    sd = {k: _to_t(v) for k, v in
          export_state_dict(text_params,
                            text_encoder_entries(cfg.text)).items()}
    missing, unexpected = text_model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert all("position_ids" in m for m in missing), missing
    L = cfg.unet.context_len
    pad = getattr(tok, "pad_token_id", tok.eos_token_id)
    ids = np.asarray([pad_ids(tok.encode(p), L, pad) for p in prompts],
                     dtype=np.int64)
    with torch.no_grad():
        return text_model(torch.from_numpy(ids)).last_hidden_state


def _ddim_constants(sc, num_steps):
    """(alphas_cumprod, grid step size, descending sampling timesteps) —
    betas/ᾱ computed independently in torch from the scheduler config."""
    betas = torch.linspace(sc.beta_start ** 0.5, sc.beta_end ** 0.5,
                           sc.num_train_timesteps,
                           dtype=torch.float64) ** 2
    acp = torch.cumprod(1.0 - betas, dim=0).float()
    step_size = sc.num_train_timesteps // num_steps
    schedule = sched_mod.schedule_from_config(num_steps, sc, kind="ddim")
    timesteps = [int(t) for t in np.asarray(schedule.timesteps)]
    return acp, step_size, timesteps


def _make_edit_hook(kind, mapper, cross_alpha, refine_alphas=None, eq_t=None,
                    self_window=(0, 0), self_max_pixels=SELF_MAX_PIXELS):
    """step → attention hook applying the reference's controller math
    (`/root/reference/main.py:85-98,162-263`), shared by every e2e loop."""
    self_lo, self_hi = self_window

    def make_hook(step):
        def hook(attn, is_cross):
            # Cond-half-only edits (`/root/reference/main.py:90-92`): the CFG
            # batch is [uncond(B); cond(B)], prompt 0 is the source.
            b = attn.shape[0] // 2
            cond = attn[b:]
            base, edits = cond[:1], cond[1:]
            if is_cross:
                if kind == "refine":
                    # Gather + existed-token blend (`/root/reference/main.py:235-239`).
                    new = base[0][:, :, mapper].permute(2, 0, 1, 3)
                    new = new * refine_alphas + edits * (1.0 - refine_alphas)
                else:
                    new = torch.einsum("hpw,bwn->bhpn", base[0], mapper)
                if eq_t is not None:
                    # Reweight on the replaced maps (`/root/reference/main.py:258-263`).
                    new = new * eq_t[:, None, None, :]
                a = cross_alpha[step]
                edits = new * a + (1.0 - a) * edits
            elif (attn.shape[2] <= self_max_pixels
                  and self_lo <= step < self_hi):
                edits = base.expand_as(edits)
            return torch.cat([attn[:b], base, edits], dim=0)
        return hook
    return make_hook


def _torch_cfg_sample(pipe, cfg, ctx, x_t, n_prompts, make_hook, guidance,
                      num_steps, vpred=False, timesteps=None, stepper=None,
                      post_step=None, return_latents=False):
    """The reference sampling loop (`/root/reference/ptp_utils.py:65-76,
    129-172`) in torch: CFG batch-doubling, hooked U-Net, latent update, VAE
    decode, uint8 — returns the (B, H, W, 3) uint8 images.

    ``stepper(step, t, eps, latents) -> latents`` overrides the per-step
    latent update (default: the DDIM closed form); pass ``timesteps`` with it
    when the scheduler walks a different grid (e.g. PLMS's T+1 warm-up).
    ``post_step(step, latents) -> latents`` is the controller's latent hook
    after the scheduler update (`controller.step_callback`,
    `/root/reference/ptp_utils.py:75`) — LocalBlend lives there.
    ``ctx`` may be a tensor or a ``step -> tensor`` callable (the null-text
    replay substitutes a different uncond embedding every step).
    ``return_latents=True`` returns the final latents and skips the VAE
    decode (latent-space comparisons at expensive scales)."""
    acp, step_size, ddim_ts = _ddim_constants(cfg.scheduler, num_steps)
    if timesteps is None:
        timesteps = ddim_ts
    latents = _to_t(np.asarray(x_t)).permute(0, 3, 1, 2).expand(
        n_prompts, -1, -1, -1)
    with torch.no_grad():
        for step, t in enumerate(timesteps):
            ctx_t = ctx(step) if callable(ctx) else ctx
            latent_in = torch.cat([latents] * 2, dim=0)
            eps = _torch_unet(pipe.unet_params, cfg.unet, latent_in, t, ctx_t,
                              make_hook(step))
            eps_uncond, eps_text = eps.chunk(2, dim=0)
            eps = eps_uncond + guidance * (eps_text - eps_uncond)
            a_t = acp[t]
            if vpred:
                # The model output is v; convert once after the (linear) CFG
                # combine: ε = √ᾱ_t·v + √(1−ᾱ_t)·x_t.
                eps = a_t.sqrt() * eps + (1 - a_t).sqrt() * latents
            if stepper is not None:
                latents = stepper(step, t, eps, latents)
            else:
                prev_t = t - step_size
                a_prev = acp[prev_t] if prev_t >= 0 else acp[0]
                x0 = (latents - (1 - a_t).sqrt() * eps) / a_t.sqrt()
                latents = a_prev.sqrt() * x0 + (1 - a_prev).sqrt() * eps
            if post_step is not None:
                latents = post_step(step, latents)
        if return_latents:
            return latents
        image = _torch_vae_decode(pipe.vae_params, cfg.vae, latents)
    img = (image.permute(0, 2, 3, 1) / 2 + 0.5).clamp(0, 1).numpy()
    return (img * 255).astype(np.uint8)


@pytest.mark.parametrize("mode", list(PROMPTS_BY_MODE))
def test_text2image_matches_torch_pipeline(mode):
    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE[mode]
    if mode == "replace_vpred":
        import dataclasses

        cfg = dataclasses.replace(
            cfg, scheduler=dataclasses.replace(
                cfg.scheduler, prediction_type="v_prediction"))
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)

    ref_ptp, ref_aligner = _reference_modules()

    # Equalizer for the reweight mode: scale the swapped word's tokens, index
    # computed by the reference's own get_word_inds.
    equalizer = None
    if mode == "reweight_on_replace":
        equalizer = np.ones((1, L), np.float32)
        inds = ref_ptp.get_word_inds(prompts[1], "dog", tok)
        equalizer[:, inds] = 2.0

    # --- ours: one jitted program -------------------------------------------
    kwargs = dict(cross_replace_steps=CROSS_REPLACE,
                  self_replace_steps=SELF_REPLACE, tokenizer=tok,
                  self_max_pixels=SELF_MAX_PIXELS, max_len=L)
    if mode in ("replace", "replace_vpred"):
        controller = factory.attention_replace(prompts, NUM_STEPS, **kwargs)
    elif mode == "refine":
        controller = factory.attention_refine(prompts, NUM_STEPS, **kwargs)
    else:
        base_ctrl = factory.attention_replace(prompts, NUM_STEPS, **kwargs)
        controller = factory.attention_reweight(
            prompts, NUM_STEPS, equalizer=jnp.asarray(equalizer),
            base=base_ctrl, **kwargs)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               guidance_scale=GUIDANCE, scheduler="ddim",
                               latent=x_t)
    got_img = np.asarray(got_img)

    # --- torch: the reference pipeline, hand-rolled --------------------------
    # Edit precompute by the reference's own host-side functions.
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, NUM_STEPS, CROSS_REPLACE, tok, max_num_words=L).float()
    if mode == "refine":
        mapper, refine_alphas = ref_aligner.get_refinement_mapper(
            prompts, tok, max_len=L)
        refine_alphas = refine_alphas.float().reshape(
            refine_alphas.shape[0], 1, 1, refine_alphas.shape[1])
    else:
        mapper = ref_aligner.get_replacement_mapper(
            prompts, tok, max_len=L).float()
    eq_t = None if equalizer is None else torch.from_numpy(equalizer)
    make_hook = _make_edit_hook(
        "refine" if mode == "refine" else "replace", mapper, cross_alpha,
        refine_alphas=refine_alphas if mode == "refine" else None, eq_t=eq_t,
        self_window=(0, int(NUM_STEPS * SELF_REPLACE)))

    # Text encode through transformers.CLIPTextModel on exported weights.
    enc = _torch_text_encode(cfg, pipe.text_params, tok,
                             list(prompts) + [""] * len(prompts))
    ctx = torch.cat([enc[len(prompts):], enc[:len(prompts)]], dim=0)  # [uncond; cond]

    want_img = _torch_cfg_sample(pipe, cfg, ctx, x_t, len(prompts), make_hook,
                                 GUIDANCE, NUM_STEPS,
                                 vpred=(mode == "replace_vpred"))

    # Same trajectory end to end: uint8 output within one quantization level.
    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_null_text_inversion_matches_torch_pipeline():
    """Null-text inversion e2e vs a hand-rolled torch loop: VAE-encode →
    T-step DDIM ascent at guidance 1 (`/root/reference/null_text.py:551-561`)
    → per-timestep Adam optimization of the uncond embedding
    (`/root/reference/null_text.py:574-606`). Early stop is disabled on both
    sides (epsilon = -inf ⇒ every inner step runs) so trajectories can be
    compared deterministically. The lr decay follows our i/(2T)
    generalization of the reference's literal 1e-2·(1−i/100) (identical at
    T=50; `p2p_tpu/engine/inversion.py:147-151`)."""
    from p2p_tpu.engine.inversion import invert

    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    prompt = "a cat riding a bike"
    num_steps = 2
    num_inner = 2
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    rng = np.random.RandomState(3)
    image = rng.uniform(-0.8, 0.8,
                        (1, cfg.image_size, cfg.image_size, 3)).astype(np.float32)

    # --- ours ---------------------------------------------------------------
    art = invert(pipe, image, prompt, num_steps=num_steps,
                 guidance_scale=GUIDANCE, num_inner_steps=num_inner,
                 early_stop_epsilon=-1e30)

    # --- torch --------------------------------------------------------------
    enc = _torch_text_encode(cfg, pipe.text_params, tok, (prompt, ""))
    cond, uncond0 = enc[:1], enc[1:]

    acp, step_size, timesteps = _ddim_constants(cfg.scheduler, num_steps)

    def alpha_at(t):
        return acp[t] if t >= 0 else acp[0]

    def ddim_prev(eps, t, x):
        a_t, a_prev = alpha_at(t), alpha_at(t - step_size)
        x0 = (x - (1 - a_t).sqrt() * eps) / a_t.sqrt()
        return a_prev.sqrt() * x0 + (1 - a_prev).sqrt() * eps

    def ddim_next(eps, t, x):
        # `/root/reference/null_text.py:481-489`: current point is one grid
        # step below t, target point is t.
        a_cur, a_next = alpha_at(t - step_size), alpha_at(t)
        x0 = (x - (1 - a_cur).sqrt() * eps) / a_cur.sqrt()
        return a_next.sqrt() * x0 + (1 - a_next).sqrt() * eps

    with torch.no_grad():
        latent = _torch_vae_encode(pipe.vae_params, cfg.vae,
                                   _to_t(image).permute(0, 3, 1, 2))
        all_latents = [latent]
        for i in range(num_steps):
            t = timesteps[num_steps - 1 - i]  # ascending
            eps = _torch_unet(pipe.unet_params, cfg.unet, latent, t, cond, None)
            latent = ddim_next(eps, t, latent)
            all_latents.append(latent)

    # Inverted terminal latent parity.
    np.testing.assert_allclose(
        np.asarray(art.x_t), all_latents[-1].permute(0, 2, 3, 1).numpy(),
        atol=2e-4, rtol=1e-3)

    # Null-text optimization parity (torch.optim.Adam vs our closed form).
    t_count = num_steps
    latent_cur = all_latents[-1]
    uncond = uncond0.clone()
    want_unconds = []
    for i, t in enumerate(timesteps):
        lr = 0.01 * (1.0 - i / (2.0 * t_count))
        with torch.no_grad():
            eps_cond = _torch_unet(pipe.unet_params, cfg.unet, latent_cur, t,
                                   cond, None)
        u = uncond.clone().requires_grad_(True)
        opt = torch.optim.Adam([u], lr=lr)
        target = all_latents[t_count - 1 - i]
        for _ in range(num_inner):
            eps_u = _torch_unet(pipe.unet_params, cfg.unet, latent_cur, t, u,
                                None)
            eps = eps_u + GUIDANCE * (eps_cond - eps_u)
            loss = torch.nn.functional.mse_loss(ddim_prev(eps, t, latent_cur),
                                                target)
            opt.zero_grad()
            loss.backward()
            opt.step()
        uncond = u.detach()
        want_unconds.append(uncond.numpy())
        with torch.no_grad():
            eps_u = _torch_unet(pipe.unet_params, cfg.unet, latent_cur, t,
                                uncond, None)
            eps = eps_u + GUIDANCE * (eps_cond - eps_u)
            latent_cur = ddim_prev(eps, t, latent_cur)

    np.testing.assert_allclose(
        art.uncond_embeddings, np.stack(want_unconds), atol=5e-4, rtol=1e-2)


def _torch_text_oracle(params, cfg, ids):
    """Generic transformer text-encoder oracle over our param pytree —
    covers the LDMBert-style tower (non-causal, gelu, no qkv bias,
    rectangular attention) that has no transformers counterpart
    (`p2p_tpu/models/text_encoder.py` spec)."""
    b, length = ids.shape
    x = _to_t(params["token_embed"])[torch.from_numpy(ids)]
    x = x + _to_t(params["pos_embed"])[:length]
    heads = cfg.num_heads
    d_head = cfg.inner_dim // heads

    def split(t):
        return t.reshape(b, length, heads, d_head).permute(0, 2, 1, 3)

    for layer in params["layers"]:
        h = _torch_layernorm(layer["ln1"])(x)
        q = split(_torch_linear(layer["q"])(h))
        k = split(_torch_linear(layer["k"])(h))
        v = split(_torch_linear(layer["v"])(h))
        sim = q @ k.transpose(-1, -2) * d_head ** -0.5
        if cfg.causal:
            sim = sim + torch.triu(
                torch.full((length, length), -1e9), diagonal=1)
        attn = torch.softmax(sim, dim=-1)
        out = (attn @ v).permute(0, 2, 1, 3).reshape(b, length, cfg.inner_dim)
        x = x + _torch_linear(layer["out"])(out)
        h = _torch_layernorm(layer["ln2"])(x)
        act = ((lambda t: t * torch.sigmoid(1.702 * t))
               if cfg.activation == "quick_gelu"
               else torch.nn.functional.gelu)
        x = x + _torch_linear(layer["fc2"])(act(_torch_linear(layer["fc1"])(h)))
    return _torch_layernorm(params["final_ln"])(x)


def _torch_vq_quantize(params, z):
    """Nearest-codebook snap (`p2p_tpu/models/vae.py:quantize` spec — the
    lookup diffusers' VQModel.decode performs)."""
    cb = _to_t(params["codebook"])                      # (K, C)
    b, c, h, w = z.shape
    flat = z.permute(0, 2, 3, 1).reshape(-1, c)         # (P, C)
    idx = torch.cdist(flat, cb).argmin(dim=1)
    return cb[idx].reshape(b, h, w, c).permute(0, 3, 1, 2)


def test_ldm_text2image_matches_torch_pipeline():
    """BASELINE config 5's backend family e2e: LDMBert-style encoder,
    per-level-heads U-Net, LDM β schedule, VQ codebook decode
    (`/root/reference/ptp_utils.py:98-126`), under an AttentionReplace
    controller — vs the hand-rolled torch loop."""
    from p2p_tpu.models import TINY_LDM

    cfg = TINY_LDM
    tok = HashWordTokenizer(vocab_size=cfg.text.vocab_size,
                            model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE["replace"]
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)

    controller = factory.attention_replace(
        prompts, NUM_STEPS, cross_replace_steps=CROSS_REPLACE,
        self_replace_steps=SELF_REPLACE, tokenizer=tok,
        self_max_pixels=SELF_MAX_PIXELS, max_len=L)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               scheduler="ddim", latent=x_t)
    got_img = np.asarray(got_img)

    ref_ptp, ref_aligner = _reference_modules()
    mapper = ref_aligner.get_replacement_mapper(prompts, tok, max_len=L).float()
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, NUM_STEPS, CROSS_REPLACE, tok, max_num_words=L).float()
    make_hook = _make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(NUM_STEPS * SELF_REPLACE)))

    # LDMBert-style tower has no transformers counterpart — encode through
    # the generic transformer oracle.
    pad = getattr(tok, "pad_token_id", tok.eos_token_id)
    ids = np.asarray([pad_ids(tok.encode(p), L, pad)
                      for p in list(prompts) + [""] * len(prompts)],
                     dtype=np.int64)
    with torch.no_grad():
        enc = _torch_text_oracle(pipe.text_params, cfg.text, ids)
    ctx = torch.cat([enc[len(prompts):], enc[:len(prompts)]], dim=0)

    # guidance falls back to cfg.guidance_scale (LDM default 5.0) on the jax
    # side; the VQ codebook snap happens inside _torch_vae_decode.
    want_img = _torch_cfg_sample(pipe, cfg, ctx, x_t, len(prompts), make_hook,
                                 cfg.guidance_scale, NUM_STEPS)

    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_text2image_plms_matches_torch_pipeline():
    """PLMS e2e — the scheduler the reference CLI inherits from the SD
    pipeline (`/root/reference/main.py:29`, `steps_offset=1`): T+1 hooked
    U-Net calls with the warm-up double evaluation, stepped on the torch side
    by the independent list-based PLMS oracle (tests/test_schedulers.py's
    PlmsSimulator, Liu et al. arXiv 2202.09778), under a Replace edit."""
    from test_schedulers import PlmsSimulator

    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE["replace"]
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)

    controller = factory.attention_replace(
        prompts, NUM_STEPS, cross_replace_steps=CROSS_REPLACE,
        self_replace_steps=SELF_REPLACE, tokenizer=tok,
        self_max_pixels=SELF_MAX_PIXELS, max_len=L)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               guidance_scale=GUIDANCE, scheduler="plms",
                               latent=x_t)
    got_img = np.asarray(got_img)

    ref_ptp, ref_aligner = _reference_modules()
    mapper = ref_aligner.get_replacement_mapper(prompts, tok, max_len=L).float()
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, NUM_STEPS, CROSS_REPLACE, tok, max_num_words=L).float()
    make_hook = _make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(NUM_STEPS * SELF_REPLACE)))

    enc = _torch_text_encode(cfg, pipe.text_params, tok,
                             list(prompts) + [""] * len(prompts))
    ctx = torch.cat([enc[len(prompts):], enc[:len(prompts)]], dim=0)

    # PLMS timesteps (T+1 with the second repeated, steps_offset=1) from our
    # schedule builder; alphas and the multistep combination come from the
    # independent simulator, plugged into the shared loop as the stepper.
    schedule = sched_mod.schedule_from_config(NUM_STEPS, cfg.scheduler,
                                              kind="plms")
    timesteps = [int(t) for t in np.asarray(schedule.timesteps)]
    acp_np = np.asarray(schedule.alphas_cumprod, dtype=np.float64)
    sim = PlmsSimulator(acp_np, schedule.step_size)

    want_img = _torch_cfg_sample(
        pipe, cfg, ctx, x_t, len(prompts), make_hook, GUIDANCE, NUM_STEPS,
        timesteps=timesteps,
        stepper=lambda step, t, eps, latents: sim(eps, int(t), latents))

    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_text2image_local_blend_matches_torch_pipeline():
    """LocalBlend e2e: a Replace edit whose latents are composited through the
    attention-derived spatial mask after every scheduler step
    (`/root/reference/main.py:33-66` base math with the null_text
    ``start_blend`` warm-up and batch-general OR,
    `/root/reference/null_text.py:39-102`). The torch loop accumulates the
    post-edit conditional cross maps at the blend resolution per step —
    exactly what our fixed-shape store slots hold — and hand-rolls the mask:
    word-weighted average → 3×3 max-pool → nearest-upsample → per-image
    max-normalize → threshold → OR with the source mask → composite."""
    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE["replace"]
    blend_words = (("cat",), ("dog",))
    blend_res = cfg.latent_size // 2        # 8: the stored mid-pyramid level
    start_blend_frac = 0.4                  # int(0.4·3)=1 ⇒ step 0 ungated
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)

    lb = factory.local_blend(prompts, blend_words, tok,
                             start_blend=start_blend_frac,
                             num_steps=NUM_STEPS, resolution=blend_res,
                             max_len=L)
    controller = factory.attention_replace(
        prompts, NUM_STEPS, cross_replace_steps=CROSS_REPLACE,
        self_replace_steps=SELF_REPLACE, tokenizer=tok,
        self_max_pixels=SELF_MAX_PIXELS, max_len=L, local_blend=lb)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               guidance_scale=GUIDANCE, scheduler="ddim",
                               latent=x_t)
    got_img = np.asarray(got_img)

    ref_ptp, ref_aligner = _reference_modules()
    mapper = ref_aligner.get_replacement_mapper(prompts, tok, max_len=L).float()
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, NUM_STEPS, CROSS_REPLACE, tok, max_num_words=L).float()
    base_make_hook = _make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(NUM_STEPS * SELF_REPLACE)))

    # One-hot word masks per prompt via the reference's own get_word_inds
    # (`/root/reference/main.py:58-64`).
    alpha_layers = torch.zeros(len(prompts), L)
    for i, (p, ws) in enumerate(zip(prompts, blend_words)):
        for w in ws:
            alpha_layers[i, ref_ptp.get_word_inds(p, w, tok)] = 1.0

    # Running store of post-edit cond-half cross maps at the blend
    # resolution, summed across steps in site call order (the reference's
    # AttentionStore accumulation, `/root/reference/main.py:135-142`).
    acc = {}
    occ = {"i": 0}
    blend_pixels = blend_res * blend_res

    def make_hook(step):
        inner = base_make_hook(step)
        occ["i"] = 0

        def hook(attn, is_cross):
            out = inner(attn, is_cross)
            if is_cross and out.shape[2] == blend_pixels:
                b = out.shape[0] // 2
                i = occ["i"]
                occ["i"] += 1
                acc[i] = acc.get(i, 0) + out[b:]
            return out
        return hook

    start_blend_steps = int(start_blend_frac * NUM_STEPS)
    n = len(prompts)

    def post_step(step, latents):
        maps = torch.cat(
            [acc[i].reshape(n, -1, blend_res, blend_res, L)
             for i in range(len(acc))], dim=1)
        weighted = (maps * alpha_layers[:, None, None, None, :]).sum(-1).mean(1)
        pooled = torch.nn.functional.max_pool2d(
            weighted[:, None], 3, stride=1, padding=1)
        up = torch.nn.functional.interpolate(
            pooled, size=latents.shape[-2:], mode="nearest")[:, 0]
        m = up / up.amax(dim=(1, 2), keepdim=True).clamp_min(1e-20)
        m = m > 0.3
        m = m[:1] | m
        mf = m[:, None].float()
        blended = latents[:1] + mf * (latents - latents[:1])
        return blended if step + 1 > start_blend_steps else latents

    enc = _torch_text_encode(cfg, pipe.text_params, tok,
                             list(prompts) + [""] * len(prompts))
    ctx = torch.cat([enc[len(prompts):], enc[:len(prompts)]], dim=0)

    want_img = _torch_cfg_sample(pipe, cfg, ctx, x_t, n, make_hook,
                                 GUIDANCE, NUM_STEPS, post_step=post_step)

    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_spatial_replace_and_negative_prompt_match_torch_pipeline():
    """The two remaining sampling-surface features e2e: SpatialReplace
    (structure injection by copying the source latent for the first
    ``(1−stop_inject)·T`` steps, `/root/reference/null_text.py:158-168`) and
    a negative prompt replacing the ``""`` unconditional text (a capability
    the reference lacks; CFG then steers away from it)."""
    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    prompts = PROMPTS_BY_MODE["replace"]
    negative = "blurry low quality"
    stop_inject = 0.4                       # inject steps 0..int(0.6·3)-1 = 0
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)

    controller = factory.spatial_replace(NUM_STEPS, stop_inject)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               guidance_scale=GUIDANCE, scheduler="ddim",
                               latent=x_t, negative_prompt=negative)
    got_img = np.asarray(got_img)

    # Torch loop: no attention edits; uncond rows encode the negative prompt;
    # the post-step hook broadcasts latent 0 while step < stop_inject steps.
    enc = _torch_text_encode(cfg, pipe.text_params, tok,
                             list(prompts) + [negative] * len(prompts))
    ctx = torch.cat([enc[len(prompts):], enc[:len(prompts)]], dim=0)
    inject_until = int((1 - stop_inject) * NUM_STEPS)

    def post_step(step, latents):
        if step < inject_until:
            return latents[:1].expand_as(latents).clone()
        return latents

    want_img = _torch_cfg_sample(pipe, cfg, ctx, x_t, len(prompts),
                                 lambda step: None, GUIDANCE, NUM_STEPS,
                                 post_step=post_step)

    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_replay_with_null_embeddings_matches_torch_pipeline():
    """The full null-text editing loop the reference's missing notebook held
    (`null_text_w_ptp.ipynb`): CFG sampling where each step's unconditional
    context is that step's optimized null embedding, under a Replace edit —
    the ``uncond_embeddings`` substitution path of `engine.sampler`
    (`/root/reference/null_text.py:618` returns the list; the notebook feeds
    it back). Here synthetic per-step embeddings stand in for an optimized
    artifact; the torch loop rebuilds the context every step."""
    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE["replace"]
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(5),
                            (1,) + pipe.latent_shape, jnp.float32)
    # Synthetic per-step null embeddings (T, 1, L, D) — what invert() returns.
    unconds = np.asarray(jax.random.normal(
        jax.random.PRNGKey(11),
        (NUM_STEPS, 1, L, cfg.text.hidden_dim), jnp.float32)) * 0.1

    controller = factory.attention_replace(
        prompts, NUM_STEPS, cross_replace_steps=CROSS_REPLACE,
        self_replace_steps=SELF_REPLACE, tokenizer=tok,
        self_max_pixels=SELF_MAX_PIXELS, max_len=L)
    got_img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               guidance_scale=GUIDANCE, scheduler="ddim",
                               latent=x_t, uncond_embeddings=jnp.asarray(unconds))
    got_img = np.asarray(got_img)

    ref_ptp, ref_aligner = _reference_modules()
    mapper = ref_aligner.get_replacement_mapper(prompts, tok, max_len=L).float()
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, NUM_STEPS, CROSS_REPLACE, tok, max_num_words=L).float()
    make_hook = _make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(NUM_STEPS * SELF_REPLACE)))

    cond = _torch_text_encode(cfg, pipe.text_params, tok, prompts)

    def ctx_at(step):
        u = torch.from_numpy(unconds[step]).expand(len(prompts), -1, -1)
        return torch.cat([u, cond], dim=0)

    want_img = _torch_cfg_sample(pipe, cfg, ctx_at, x_t, len(prompts),
                                 make_hook, GUIDANCE, NUM_STEPS)

    diff = np.abs(got_img.astype(np.int32) - want_img.astype(np.int32))
    assert diff.max() <= 1, (
        f"max pixel diff {diff.max()}, mean {diff.mean():.4f}")
    assert diff.mean() < 0.05


def test_text2image_short_loop_matches_torch_at_sd14_scale():
    """The loop × scale seam: the controlled CFG
    sampling loop at the REAL SD-1.4 topology (860M-param U-Net, 64² latent,
    77×768 context) for 2 steps, ours vs the torch reference loop — scan
    carry dtypes, scheduler constants, and controller gather shapes at real
    shapes, composing the families `test_full_*_sd14_scale` (full scale, one
    forward) and `test_text2image_matches_torch_pipeline` (full loop, tiny)
    left separate. Latent-space comparison through a jitted
    `_denoise_scan` — the exact scan program both `text2image` and the dp
    sweep compile — with no VAE decode on either side: the 512² decode is
    covered at full scale by
    `test_full_vae_matches_torch_oracle_sd14_scale`."""
    from p2p_tpu.engine.sampler import _denoise_scan
    from p2p_tpu.models.config import SD14, unet_layout
    from p2p_tpu.ops import schedulers as _sched

    cfg = SD14
    steps = 2
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    L = cfg.unet.context_len
    prompts = PROMPTS_BY_MODE["replace"]
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(30), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(31), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(32), cfg.vae),
        tokenizer=tok,
    )
    x_t = jax.random.normal(jax.random.PRNGKey(33),
                            (1,) + pipe.latent_shape, jnp.float32)

    controller = factory.attention_replace(
        prompts, steps, cross_replace_steps=CROSS_REPLACE,
        self_replace_steps=SELF_REPLACE, tokenizer=tok,
        self_max_pixels=SELF_MAX_PIXELS, max_len=L)

    # --- ours: the jitted loop at full scale, final latents out ----------
    from p2p_tpu.engine.sampler import encode_prompts as _enc

    n = len(prompts)
    ctx_c = _enc(pipe, prompts)
    ctx_u = _enc(pipe, [""] * n)
    ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)
    lats0 = jnp.broadcast_to(x_t, (n,) + x_t.shape[1:])
    layout = unet_layout(cfg.unet)
    schedule = _sched.schedule_from_config(steps, cfg.scheduler, kind="ddim")

    @jax.jit
    def run_scan(p, c, lat, ctrl, gs):
        lat, _ = _denoise_scan(p, cfg, layout, schedule, "ddim", c, lat,
                               ctrl, gs)
        return lat

    got_final = np.asarray(run_scan(pipe.unet_params, ctx, lats0, controller,
                                    jnp.float32(GUIDANCE)))

    # --- torch: the reference loop at the same scale, no decode ----------
    ref_ptp, ref_aligner = _reference_modules()
    mapper = ref_aligner.get_replacement_mapper(prompts, tok,
                                                max_len=L).float()
    cross_alpha = ref_ptp.get_time_words_attention_alpha(
        prompts, steps, CROSS_REPLACE, tok, max_num_words=L).float()
    make_hook = _make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(steps * SELF_REPLACE)))

    enc = _torch_text_encode(cfg, pipe.text_params, tok,
                             list(prompts) + [""] * n)
    ctx_t = torch.cat([enc[n:], enc[:n]], dim=0)

    want_final = _torch_cfg_sample(
        pipe, cfg, ctx_t, x_t, n, make_hook, GUIDANCE, steps,
        return_latents=True).permute(0, 2, 3, 1).numpy()

    # Two full-scale CFG steps compound the single-forward f32 drift
    # (atol 2e-4 at one forward, guidance 7.5 amplifies the eps delta).
    np.testing.assert_allclose(got_final, want_final, atol=5e-3, rtol=1e-2)
    # And the trajectory is genuinely edited + controlled, not degenerate.
    assert not np.allclose(got_final[0], got_final[1])
