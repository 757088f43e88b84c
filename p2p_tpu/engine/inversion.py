"""Null-text inversion: DDIM inversion + per-step uncond-embedding optimization.

Behavioral spec: `/root/reference/null_text.py:447-630`. The reference drives
~50 + 50×(10×2) U-Net forwards and 500 Adam steps from Python; here the whole
procedure is **two compiled programs**:

1. :func:`ddim_invert` — a ``lax.scan`` over ascending timesteps with
   guidance 1 (cond-only ε, `/root/reference/null_text.py:499,558`), recording
   all T+1 latents.
2. :func:`null_optimize` — a ``lax.scan`` over the T outer steps; each step
   re-initializes Adam state over the uncond embedding and runs a
   ``lax.while_loop`` of ≤``num_inner_steps`` gradient iterations with the
   reference's decaying lr ``1e-2·(1−i/100)`` and early-stop threshold
   ``eps + i·2e-5`` (`/root/reference/null_text.py:574-606`).

The result is a serializable artifact (x_T + per-step uncond embeddings):
expensive to compute, reusable across many edits of the same image — the
persistence the reference never had (SURVEY §5 checkpoint/resume).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import vae as vae_mod
from ..models.conditioning import context_of, with_context
from ..models.config import PipelineConfig
from ..models.denoiser import require_unet
from ..models.unet import apply_unet, embed_added
from ..ops import schedulers as sched_mod
from ..utils import progress as progress_mod
from .sampler import Pipeline, encode_prompts


@dataclasses.dataclass
class InversionArtifact:
    """Persistable output of :func:`invert`: everything needed to replay the
    image under CFG editing (`/root/reference/null_text.py:618` returns these
    in memory and loses them on exit)."""

    x_t: np.ndarray                  # (1, h, w, c) inverted terminal latent
    uncond_embeddings: np.ndarray    # (T, 1, L, D) per-step optimized uncond
    prompt: str
    num_steps: int
    image_gt: Optional[np.ndarray] = None   # (H, W, 3) uint8
    image_rec: Optional[np.ndarray] = None  # VAE round-trip reconstruction

    def save(self, path: str) -> None:
        np.savez(path, x_t=self.x_t, uncond_embeddings=self.uncond_embeddings,
                 prompt=np.asarray(self.prompt), num_steps=self.num_steps,
                 image_gt=self.image_gt if self.image_gt is not None else np.zeros(0),
                 image_rec=self.image_rec if self.image_rec is not None else np.zeros(0))

    @classmethod
    def load(cls, path: str) -> "InversionArtifact":
        z = np.load(path, allow_pickle=False)
        gt = z["image_gt"]
        rec = z["image_rec"]
        return cls(x_t=z["x_t"], uncond_embeddings=z["uncond_embeddings"],
                   prompt=str(z["prompt"]), num_steps=int(z["num_steps"]),
                   image_gt=gt if gt.size else None,
                   image_rec=rec if rec.size else None)


def load_image(path: str, size: int = 512, left: int = 0, right: int = 0,
               top: int = 0, bottom: int = 0) -> np.ndarray:
    """Crop-then-resize to (size, size, 3) uint8 — `/root/reference/
    null_text.py:447-466` (with its `top = min(top, h - left - 1)` copy-paste
    bug fixed: offsets clamp against their own axis)."""
    from PIL import Image

    img = np.array(Image.open(path).convert("RGB"))
    h, w = img.shape[:2]
    left = min(left, w - 1)
    right = min(right, w - left - 1)
    top = min(top, h - 1)
    bottom = min(bottom, h - top - 1)
    img = img[top:h - bottom, left:w - right]
    h, w = img.shape[:2]
    if h < w:
        off = (w - h) // 2
        img = img[:, off:off + h]
    elif w < h:
        off = (h - w) // 2
        img = img[off:off + w]
    img = np.array(Image.fromarray(img).resize((size, size)))
    return img


@partial(jax.jit, static_argnames=("cfg", "progress", "sp", "metrics"))
def _ddim_invert_jit(unet_params, vae_params, cfg: PipelineConfig,
                     schedule: sched_mod.DiffusionSchedule,
                     image: jax.Array, cond,
                     progress: bool = False, sp=None, metrics: bool = False):
    """image (1,H,W,3) in [-1,1] → all T+1 latents, ascending noise."""
    latent0 = vae_mod.encode(vae_params, cfg.vae, image)
    cond = embed_added(unet_params, cfg.unet, cond)

    # Ascending timesteps: reversed sampling order
    # (`/root/reference/null_text.py:555-560` uses timesteps[-(i+1)]).
    ts = schedule.timesteps[::-1]

    def body(latent, scan_in):
        i, t = scan_in
        progress_mod.emit_step(progress or metrics, i, phase="invert",
                               report=progress)
        eps, _ = apply_unet(unet_params, cfg.unet, latent, t, cond, sp=sp)
        eps = sched_mod.to_epsilon(schedule, eps, t, latent)
        nxt = sched_mod.ddim_next_step(schedule, eps, t, latent)
        return nxt, nxt

    idx = jnp.arange(ts.shape[0], dtype=jnp.int32)
    x_t, all_latents = jax.lax.scan(body, latent0, (idx, ts))
    return latent0, x_t, jnp.concatenate([latent0[None], all_latents], axis=0)


def _adam_update(g, m, v, j, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step (matches torch.optim.Adam defaults,
    `/root/reference/null_text.py:582`)."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * (g * g)
    mhat = m / (1 - b1 ** j)
    vhat = v / (1 - b2 ** j)
    return -lr * mhat / (jnp.sqrt(vhat) + eps), m, v


@partial(jax.jit, static_argnames=("cfg", "num_inner_steps", "progress",
                                   "sp", "metrics"))
def _null_optimize_jit(unet_params, cfg: PipelineConfig,
                       schedule: sched_mod.DiffusionSchedule,
                       latents: jax.Array,        # (T+1, 1, h, w, c) ascending
                       uncond0,                   # (1, L, D) "" embedding
                       cond,                      # (1, L, D) prompt embedding
                       guidance_scale: jax.Array,
                       num_inner_steps: int,
                       epsilon: jax.Array,
                       progress: bool = False, sp=None,
                       metrics: bool = False):
    """Per-timestep uncond-embedding optimization
    (`/root/reference/null_text.py:574-606`). Returns (T, 1, L, D) f32.

    The optimized embedding and its Adam state live in f32 whatever the
    model compute dtype (the reference optimizes a f32 torch tensor); the
    embedding is cast to the model dtype at each U-Net application. This is
    also what keeps the while_loop carry well-typed on the bf16 TPU path —
    Adam's f32 scalar schedule would otherwise promote the update and break
    the carry contract.

    ``uncond0`` and ``cond`` are the preset's conditioning
    (``models.conditioning``). What is optimised is the hidden states; a
    pooled vector beside them stays as the empty prompt's was encoded."""
    t_count = schedule.timesteps.shape[0]
    model_dtype = context_of(cond).dtype
    cond = embed_added(unet_params, cfg.unet, cond)
    null = embed_added(unet_params, cfg.unet, uncond0)

    def uncond(u):
        """The unconditional conditioning with hidden states ``u``."""
        return with_context(null, u.astype(model_dtype))

    uncond0 = context_of(uncond0).astype(jnp.float32)

    def outer(carry, scan_in):
        latent_cur, u_cur = carry
        i, t = scan_in
        progress_mod.emit_step(progress or metrics, i, phase="null_text",
                               report=progress)
        # Reference decay is the literal `1e-2 * (1 - i/100)` at T=50
        # (`/root/reference/null_text.py:582`) — i.e. lr halves over the run.
        # Generalized as i/(2T): identical numbers at T=50, and the schedule
        # stays positive/meaningful for any other step count.
        lr = 0.01 * (1.0 - i.astype(jnp.float32) / (2.0 * t_count))
        stop_at = epsilon + i.astype(jnp.float32) * 2e-5
        # Target: the recorded inversion latent one step less noisy
        # (`/root/reference/null_text.py:584` latents[len - i - 2]).
        target = jax.lax.dynamic_index_in_dim(
            latents, t_count - 1 - i, axis=0, keepdims=False)
        eps_cond, _ = apply_unet(unet_params, cfg.unet, latent_cur, t, cond,
                                 sp=sp)
        eps_cond = jax.lax.stop_gradient(eps_cond)
        # The loss's step math and compare run in f32 whatever the model
        # dtype (only the U-Net forwards stay in model dtype): on the bf16
        # path a bf16 (prev - target) would bottom out at ~1e-5 quantization
        # noise — the same magnitude as early_stop_epsilon, turning the
        # early-stop into a coin flip. ddim_step computes in f32 internally
        # and casts to its sample's dtype, so feed it the f32 latent.
        latent_f = latent_cur.astype(jnp.float32)
        target_f = target.astype(jnp.float32)

        def loss_fn(u):
            eps_u, _ = apply_unet(unet_params, cfg.unet, latent_cur, t,
                                  uncond(u), sp=sp)
            eps = eps_u + guidance_scale * (eps_cond - eps_u)
            eps = sched_mod.to_epsilon(schedule, eps, t, latent_cur)
            prev = sched_mod.ddim_step(schedule, eps, t, latent_f)
            return jnp.mean(jnp.square(prev - target_f))

        def inner_cond(state):
            _, _, _, j, loss = state
            return jnp.logical_and(j < num_inner_steps, loss >= stop_at)

        def inner_body(state):
            u, m, v, j, _ = state
            loss, g = jax.value_and_grad(loss_fn)(u)
            upd, m, v = _adam_update(g, m, v, j + 1.0, lr)
            # Early-stop semantics of the reference: it breaks *after* the
            # step when the post-step loss clears the bar; we keep the update
            # unconditionally and re-test in inner_cond, same fixed point.
            return (u + upd, m, v, j + 1.0, loss)

        init = (u_cur, jnp.zeros_like(u_cur), jnp.zeros_like(u_cur),
                jnp.float32(0.0), jnp.float32(jnp.inf))
        u_opt, _, _, j_done, _ = jax.lax.while_loop(inner_cond, inner_body,
                                                    init)
        # Inner-iteration telemetry: how many Adam steps each outer step
        # actually ran before the early-stop bar (the distribution is the
        # knob num_inner_steps should be tuned against). Traced value,
        # static tag; nothing is traced in when metrics is off.
        progress_mod.emit_event(metrics, "invert.inner_steps", j_done)

        # Advance with the optimized uncond under full CFG
        # (`/root/reference/null_text.py:602-604`).
        eps_u, _ = apply_unet(unet_params, cfg.unet, latent_cur, t,
                              uncond(u_opt), sp=sp)
        eps = eps_u + guidance_scale * (eps_cond - eps_u)
        eps = sched_mod.to_epsilon(schedule, eps, t, latent_cur)
        latent_next = sched_mod.ddim_step(schedule, eps, t, latent_cur)
        return (latent_next, u_opt), u_opt

    steps = jnp.arange(t_count, dtype=jnp.int32)
    x_t = latents[-1]
    (_, _), uncond_list = jax.lax.scan(
        outer, (x_t, uncond0), (steps, schedule.timesteps))
    return uncond_list


def invert(
    pipe: Pipeline,
    image: np.ndarray,            # (H, W, 3) uint8 or (1, H, W, 3) float [-1,1]
    prompt: str,
    *,
    num_steps: int = 50,
    guidance_scale: Optional[float] = None,
    num_inner_steps: int = 10,
    early_stop_epsilon: float = 1e-5,
    dtype=jnp.float32,
    progress: bool = False,
    sp=None,
    gate=None,
    metrics: bool = False,
) -> InversionArtifact:
    """Full null-text inversion (`/root/reference/null_text.py:608-618`):
    DDIM-invert with guidance 1, then optimize per-step uncond embeddings so
    CFG sampling at full guidance reproduces the input image.

    ``gate`` exists only to force the phase-gating decision explicitly: the
    null-text procedure optimizes a *per-step* uncond embedding at every DDIM
    step, so CFG truncation (``gate < T``) has no valid interpretation here —
    any value other than ``None``/``num_steps`` is rejected. Replays of the
    artifact are likewise gate-free (``text2image`` rejects ``gate`` whenever
    ``uncond_embeddings`` are active).

    ``sp`` (a :class:`p2p_tpu.models.unet.SpConfig`) shards large
    self-attention sites with ring attention through both compiled
    programs — including the optimization's gradient, which recomputes
    ring-flash blocks through the einsum VJP (`parallel/ring.py`). The
    long-context path for inverting high-resolution images.

    ``metrics`` traces the telemetry callbacks into both programs
    (phase-tagged step timing plus the per-outer-step inner-iteration count
    as an ``invert.inner_steps`` host event); collected when the caller
    installed ``obs.device.instrument`` (the CLI ``--metrics`` flag does).
    Disabled, both compiled programs are unchanged."""
    if gate is not None and gate != num_steps:
        raise ValueError(
            f"null-text inversion is incompatible with phase-gated sampling "
            f"(gate={gate!r}): the optimization targets a per-step uncond "
            "embedding at every DDIM step, which CFG truncation would drop. "
            "Run invert() with gate=None; apply --gate to plain "
            "generation/editing only.")
    cfg = pipe.config
    require_unet(cfg.unet, "null-text inversion")
    gs = jnp.asarray(cfg.guidance_scale if guidance_scale is None else guidance_scale,
                     jnp.float32)
    if image.dtype == np.uint8:
        image_f = image.astype(np.float32) / 127.5 - 1.0
    else:
        image_f = np.asarray(image, np.float32)
    if image_f.ndim == 3:
        image_f = image_f[None]
    image_j = jnp.asarray(image_f, dtype)

    # Always DDIM (`/root/reference/null_text.py:23` — the null-text path is
    # DDIM-only), but β/α constants come from the backend's scheduler config.
    schedule = sched_mod.schedule_from_config(num_steps, cfg.scheduler, kind="ddim")
    cond = encode_prompts(pipe, [prompt], dtype=dtype)
    uncond0 = encode_prompts(pipe, [""], dtype=dtype)

    from ..obs.spans import span

    if progress:
        progress_mod.activate(num_steps, "ddim-invert")
    with span("invert.ddim", steps=num_steps):
        latent0, x_t, all_latents = _ddim_invert_jit(
            pipe.unet_params, pipe.vae_params, cfg, schedule, image_j, cond,
            progress=progress, sp=sp, metrics=metrics)

    if progress:
        # activate() drains phase-1 callbacks first (block_until_ready only
        # waits on the computation, not on host callback delivery).
        progress_mod.activate(num_steps, "null-text opt")
    with span("invert.null_optimize", steps=num_steps,
              inner_steps=num_inner_steps):
        uncond_list = _null_optimize_jit(
            pipe.unet_params, cfg, schedule, all_latents, uncond0, cond, gs,
            num_inner_steps, jnp.float32(early_stop_epsilon),
            progress=progress, sp=sp, metrics=metrics)

    rec = vae_mod.to_uint8(vae_mod.decode(
        pipe.vae_params, cfg.vae, latent0.astype(jnp.float32)))

    gt = image if image.dtype == np.uint8 else vae_mod.to_uint8(
        jnp.asarray(image_f))[0]
    return InversionArtifact(
        x_t=np.asarray(x_t),
        uncond_embeddings=np.asarray(uncond_list),
        prompt=prompt,
        num_steps=num_steps,
        image_gt=np.asarray(gt).reshape(image_f.shape[1:]) if np.asarray(gt).size else None,
        image_rec=np.asarray(rec)[0],
    )
