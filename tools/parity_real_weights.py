"""Real-weights parity harness: one command, per-stage max-abs report.

    python tools/parity_real_weights.py /path/to/stable-diffusion-v1-4 \
        --preset sd14 --steps 3 --out-dir parity_out/

Loads a diffusers-format checkpoint directory into OUR pipeline
(`p2p_tpu.models.checkpoint.load_pipeline` — the path the reference gets
from `StableDiffusionPipeline.from_pretrained`, `/root/reference/main.py:29`)
and runs the BASELINE config-1 AttentionReplace edit twice: once through our
jitted `text2image`, once through the independent hand-rolled torch
reference loop the e2e parity suite maintains
(`tests/test_e2e_parity_torch.py`, spec
`/root/reference/ptp_utils.py:65-76,129-172` + `main.py:85-98,162-230`).
Writes both images plus `report.json` with a per-stage max-abs breakdown:

    text_encoder   last_hidden_state, ours vs torch tower
    unet_eps       one CFG U-Net forward at the first timestep
    loop_latent    final latent after the full controlled sampling loop
    vae_decode     the torch loop's final latent decoded through both VAEs
                   (f32 image — isolates the decoder from loop drift)
    image          final uint8 images (max + mean pixel diff)

Exit 0 iff the uint8 images agree within one quantization level — the
"pixel-matching the PyTorch reference" criterion (BASELINE.json:5). No
pretrained weights ship in this repo; the harness is exercised end-to-end
against an HF-format random-weight checkpoint by
`tests/test_parity_harness.py`, so the day real weights are available this
is a 5-minute check (docs/CHECKPOINTS.md §"Real-weights parity").
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def main(argv=None):
    from p2p_tpu.models.config import PRESET_CONFIGS

    ap = argparse.ArgumentParser(
        description="Per-stage parity of a real checkpoint vs the torch "
                    "reference loop")
    ap.add_argument("checkpoint", help="diffusers-format checkpoint dir")
    ap.add_argument("--preset", default="sd14", choices=tuple(PRESET_CONFIGS))
    ap.add_argument("--prompts", nargs=2,
                    default=["a squirrel eating a burger",
                             "a squirrel eating a lasagna"],
                    help="source and edit prompt (same word count: Replace)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--guidance", type=float, default=None,
                    help="default: the preset's guidance scale")
    ap.add_argument("--cross-replace", type=float, default=0.8)
    ap.add_argument("--self-replace", type=float, default=0.4)
    ap.add_argument("--out-dir", default="parity_out")
    ap.add_argument("--dpm-operating-point", action="store_true",
                    help="also render DDIM-50 vs DPM-20 from the same x_T "
                         "through our pipeline (side-by-side PNGs + PSNR) — "
                         "the image-level leg of PERF.md's quality-matched "
                         "operating point, meaningful on trained weights")
    ap.add_argument("--device", choices=("cpu", "default"), default="cpu",
                    help="cpu (default): force the jax CPU backend so both "
                         "sides run f32 on the same hardware; 'default' "
                         "keeps the ambient backend (expect bf16-scale "
                         "drift on TPU)")
    args = ap.parse_args(argv)

    import jax

    if args.device == "cpu":
        # The backend initializes lazily, so this still takes effect after
        # `import jax`.
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp
    from PIL import Image

    from p2p_tpu.controllers import factory
    from p2p_tpu.models.checkpoint import load_pipeline
    from p2p_tpu.models.unet import apply_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.ops import schedulers as sched_mod
    from p2p_tpu.utils.tokenizer import pad_ids

    # The independent torch reference loop the e2e suite maintains.
    import test_e2e_parity_torch as O
    torch = O.torch

    cfg = PRESET_CONFIGS[args.preset]
    guidance = cfg.guidance_scale if args.guidance is None else args.guidance
    prompts = list(args.prompts)
    steps = args.steps
    L = cfg.unet.context_len
    vpred = cfg.scheduler.prediction_type == "v_prediction"

    print(f"loading {args.checkpoint} as {cfg.name} ...", flush=True)
    pipe = load_pipeline(args.checkpoint, cfg)
    tok = pipe.tokenizer

    report = {"checkpoint": os.path.abspath(args.checkpoint),
              "preset": args.preset, "prompts": prompts, "steps": steps,
              "guidance": guidance, "seed": args.seed, "stages": {}}

    def stage(name, ours, theirs, note=""):
        d = float(np.max(np.abs(np.asarray(ours, np.float32)
                                - np.asarray(theirs, np.float32))))
        report["stages"][name] = {"max_abs": d, **({"note": note} if note else {})}
        print(f"  [{name}] max|ours - torch| = {d:.3e} {note}", flush=True)
        return d

    # --- stage 1: text encoder -------------------------------------------
    from p2p_tpu.engine.sampler import encode_prompts

    all_prompts = prompts + [""] * len(prompts)
    ours_enc = encode_prompts(pipe, all_prompts)
    if cfg.text.arch == "ldmbert":
        pad = getattr(tok, "pad_token_id", tok.eos_token_id)
        ids = np.asarray([pad_ids(tok.encode(p), L, pad) for p in all_prompts],
                         dtype=np.int64)
        with torch.no_grad():
            torch_enc = O._torch_text_oracle(pipe.text_params, cfg.text, ids)
    else:
        torch_enc = O._torch_text_encode(cfg, pipe.text_params, tok,
                                         all_prompts)
    stage("text_encoder", ours_enc, torch_enc.numpy())

    # --- shared latent + contexts ----------------------------------------
    x_t = jax.random.normal(jax.random.PRNGKey(args.seed),
                            (1,) + pipe.latent_shape, jnp.float32)
    n = len(prompts)
    ctx_torch = torch.cat([torch_enc[n:], torch_enc[:n]], dim=0)

    # --- stage 2: one CFG U-Net forward at the first timestep ------------
    schedule = sched_mod.schedule_from_config(steps, cfg.scheduler,
                                              kind="ddim")
    t0 = int(np.asarray(schedule.timesteps)[0])
    lat_b = jnp.broadcast_to(x_t, (2 * n,) + x_t.shape[1:])
    ours_eps, _ = apply_unet(
        pipe.unet_params, cfg.unet, lat_b, jnp.int32(t0),
        jnp.concatenate([ours_enc[n:], ours_enc[:n]], axis=0))
    lat_t = O._to_t(np.asarray(x_t)).permute(0, 3, 1, 2).expand(
        2 * n, -1, -1, -1)
    with torch.no_grad():
        torch_eps = O._torch_unet(pipe.unet_params, cfg.unet, lat_t, t0,
                                  ctx_torch, None)
    stage("unet_eps", ours_eps,
          torch_eps.permute(0, 2, 3, 1).numpy())

    # --- stage 3+5: the full controlled loop -----------------------------
    # Ours rides the dp sweep engine at G=1 — the same `_denoise_scan`
    # program `text2image` compiles (pinned equal by tests/test_parallel.py)
    # but returning the final latents the loop_latent stage needs.
    from p2p_tpu.parallel import sweep

    controller = factory.attention_replace(
        prompts, steps, cross_replace_steps=args.cross_replace,
        self_replace_steps=args.self_replace, tokenizer=tok,
        self_max_pixels=O.SELF_MAX_PIXELS, max_len=L)
    ctrls = jax.tree_util.tree_map(lambda a: a[None], controller)
    ctx_ours = jnp.concatenate([ours_enc[n:], ours_enc[:n]], axis=0)
    lats0 = jnp.broadcast_to(x_t, (n,) + x_t.shape[1:])
    ours_imgs, ours_final = sweep(pipe, ctx_ours[None], lats0[None], ctrls,
                                  num_steps=steps, guidance_scale=guidance,
                                  scheduler="ddim")
    ours_img = np.asarray(ours_imgs[0])
    ours_final = np.asarray(ours_final[0])

    # Edit precompute: the reference's own host-side functions when the
    # checkout is present, else our parity-pinned equivalents.
    mapper = cross_alpha = None
    if os.path.isdir(O.REFERENCE_DIR):
        sys.path.insert(0, O.REFERENCE_DIR)
        try:
            import ptp_utils as ref_ptp
            import seq_aligner as ref_aligner

            m = ref_aligner.get_replacement_mapper(
                prompts, tok, max_len=L).float()
            a = ref_ptp.get_time_words_attention_alpha(
                prompts, steps, args.cross_replace, tok,
                max_num_words=L).float()
            mapper, cross_alpha = m, a  # atomic: both or fall back to ours
            report["edit_precompute"] = "reference"
        except Exception as e:
            print(f"  (reference precompute unavailable: {e})", flush=True)
        finally:
            sys.path.remove(O.REFERENCE_DIR)
    if mapper is None:
        from p2p_tpu.align.aligner import get_replacement_mapper
        from p2p_tpu.align.words import get_time_words_attention_alpha

        mapper = torch.from_numpy(np.asarray(
            get_replacement_mapper(prompts, tok, max_len=L), np.float32))
        cross_alpha = torch.from_numpy(np.asarray(
            get_time_words_attention_alpha(
                prompts, steps, args.cross_replace, tok, max_num_words=L),
            np.float32))
        report["edit_precompute"] = "p2p_tpu.align (reference unavailable)"

    make_hook = O._make_edit_hook(
        "replace", mapper, cross_alpha,
        self_window=(0, int(steps * args.self_replace)))

    final_lat = {}

    def capture_post_step(step, latents):
        # Runs after the helper's own (unduplicated) DDIM update.
        final_lat["lat"] = latents
        return latents

    torch_img = O._torch_cfg_sample(
        pipe, cfg, ctx_torch, x_t, n, make_hook, guidance, steps,
        vpred=vpred, post_step=capture_post_step)

    torch_final = final_lat["lat"]
    stage("loop_latent", ours_final,
          torch_final.permute(0, 2, 3, 1).numpy(),
          note=f"(after {steps} controlled CFG steps)")

    # --- stage 4: VAE decode of the torch loop's final latent through both
    ours_dec = vae_mod.decode(
        pipe.vae_params, cfg.vae,
        jnp.asarray(torch_final.permute(0, 2, 3, 1).numpy()))
    with torch.no_grad():
        torch_dec = O._torch_vae_decode(pipe.vae_params, cfg.vae, torch_final)
    stage("vae_decode", ours_dec,
          torch_dec.permute(0, 2, 3, 1).numpy(),
          note="(f32 image in [-1,1], shared input latent)")

    # --- stage 5: final images -------------------------------------------
    diff = np.abs(ours_img.astype(np.int32) - torch_img.astype(np.int32))
    report["stages"]["image"] = {"max_abs": int(diff.max()),
                                 "mean_abs": float(diff.mean())}
    print(f"  [image] max pixel diff = {diff.max()}, "
          f"mean = {diff.mean():.5f}", flush=True)

    os.makedirs(args.out_dir, exist_ok=True)
    for i in range(n):
        Image.fromarray(ours_img[i]).save(
            os.path.join(args.out_dir, f"ours_{i}.png"))
        Image.fromarray(torch_img[i]).save(
            os.path.join(args.out_dir, f"torch_ref_{i}.png"))

    if args.dpm_operating_point:
        # Image-level check of PERF.md's quality-matched operating point
        # (DPM-Solver++(2M) @ 20 steps ≈ DDIM @ 50): same x_T, both solvers
        # through OUR pipeline, side-by-side PNGs + PSNR between them. On
        # random weights the ε-field is not smooth in λ so the numbers are
        # meaningless (tests/test_dpm_quality.py pins why); on real weights
        # this is the missing image-level leg of that argument.
        from p2p_tpu.engine.sampler import text2image

        ddim_steps, dpm_steps = ((4, 2) if args.preset
                                 in ("tiny", "tiny_ldm") else (50, 20))
        pair = {}
        for kind, ksteps in (("ddim", ddim_steps), ("dpm", dpm_steps)):
            kimg, _, _ = text2image(pipe, prompts[:1], None,
                                    num_steps=ksteps, scheduler=kind,
                                    guidance_scale=guidance, latent=x_t)
            pair[kind] = np.asarray(kimg[0])
            Image.fromarray(pair[kind]).save(os.path.join(
                args.out_dir, f"quality_{kind}{ksteps}.png"))
        mse = float(np.mean((pair["ddim"].astype(np.float32)
                             - pair["dpm"].astype(np.float32)) ** 2))
        psnr = float("inf") if mse == 0 else 10 * np.log10(255.0 ** 2 / mse)
        report["dpm_operating_point"] = {
            "ddim_steps": ddim_steps, "dpm_steps": dpm_steps,
            "psnr_db": round(psnr, 2),
            "note": "image-level leg of PERF.md's DPM-20≈DDIM-50 claim; "
                    "only meaningful on trained weights"}
        print(f"  [dpm_operating_point] DDIM-{ddim_steps} vs DPM-{dpm_steps}"
              f" PSNR = {psnr:.2f} dB", flush=True)

    ok = diff.max() <= 1
    report["pass"] = bool(ok)
    with open(os.path.join(args.out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(f"report + images written to {args.out_dir}/", flush=True)
    print("PARITY PASS" if ok else "PARITY FAIL (max pixel diff > 1)",
          flush=True)
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
