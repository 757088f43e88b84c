"""Headline benchmark: 50-step SD-v1.4 512² AttentionReplace 2-prompt edit.

Prints JSON lines {"metric", "value", "unit", "platform", "vs_baseline", ...}
— the current best after every completed variant, the last one being the
result — from ONE process: `main` parses `--preset`, calls `_measure` in this
process and returns its code. There is no fallback: `--preset sd14` (and
`auto`, which means sd14) exits non-zero without a TPU and prints no metric
line, and a block that fails or is skipped (`note()`) makes every preset
exit non-zero.

The operating-point sweep: the batched variant vmaps g independent edit
groups (g ∈ {2, 4, 8} as time allows; U-Net batch 4g with CFG); the best
variant is reported by name and the headline value stays the spec'd 50-step
DDIM Replace workload. Budget-gated secondaries then cover every other
BASELINE.json config and the quality-matched operating point, as extras in
the same JSON line:

  batched_4groups_gate05_imgs_per_s      (phase-gated sampling, gate=0.5T:
      single-branch U-Net + cached cross-attention past the gate; carries
      gate_step, phase{1,2}_ms_per_step and phase2_unet_batch so the
      trajectory separates algorithmic wins from kernel wins)
  gate.kernel                            (fused in-kernel-edit attention A/B:
      fused vs materialized vs library-flash-floor ms/step, per-variant MFU
      and the fused/materialized speedup — benchwatch's gate.kernel.speedup)
  dpm20_imgs_per_s / dpm20_batched_{8,4}groups_imgs_per_s  (DPM-Solver++(2M)
      20 steps ≈ 50-step-DDIM quality, PERF.md)
  reweight_eqsweep_4groups_imgs_per_s    (config 3: equalizer sweep)
  refine_localblend_imgs_per_s           (config 2: Refine + LocalBlend)
  ldm256_8prompt_imgs_per_s              (config 5: LDM-256 backend)
  nullinv_s_per_image                    (config 4: null-text inversion)

`--preset rehearse` (with JAX_PLATFORMS=cpu) runs every one of these blocks
at tiny scale — the CPU CI for the bench itself; `--preset tiny` is the
single-group smoke. Neither prints a device metric: their line says
"platform": "cpu".

P2P_BENCH_SECONDARIES=ldm256,nullinv (comma list; see _BLOCK_KEYS) narrows
a real sd14 run to the named blocks. Ignored under rehearsal (its CI must
cover all blocks) and by tiny.

Baseline: ≥4 img/s/chip on TPU (driver north star, BASELINE.md). Weights are
random-init (no checkpoint in the image) — throughput is weight-agnostic.
"""

import argparse
import json
import os
import sys
import time

# Block keys P2P_BENCH_SECONDARIES may name (comma-separated). "gsweep" is
# the batched operating-point sweep; the rest are the budget-gated
# secondaries in their run order. "gate" is the phase-gated variant of the
# headline batched-4-groups config (cross-attention caching + CFG truncation
# past the gate step — an *algorithmic* win, reported with per-phase ms/step
# so the trajectory can tell it apart from kernel wins). "kernel" is the
# fused in-kernel-edit attention A/B (ISSUE 16, the gate.kernel sub-record).
_BLOCK_KEYS = ("gsweep", "gate", "kernel", "dpm", "dpm_batched", "reweight",
               "refine_blend", "ldm256", "serve", "obs", "cost",
               "resilience", "nullinv")


def _secondaries_filter(preset, env_value):
    """Parse P2P_BENCH_SECONDARIES into the set of blocks to run, or None
    for "run everything".

    Chip time is budgeted; a run that only needs some blocks names them.
    Honored only for the real sd14 measurement: rehearsal must always run
    every block (a stray env var must not turn the bench's CI green while
    skipping blocks — same rule as the budget gates), and tiny has no
    secondaries to filter."""
    if preset != "sd14" or not env_value:
        return None
    keys = set(k.strip() for k in env_value.split(",") if k.strip())
    unknown = keys - set(_BLOCK_KEYS)
    if unknown or not keys:
        # A comma/whitespace-only value must error like a typo does — an
        # empty filter would silently skip every block, exactly the silent
        # narrowing this validation exists to prevent.
        raise SystemExit(
            f"P2P_BENCH_SECONDARIES: "
            f"{'unknown block(s) ' + str(sorted(unknown)) if unknown else 'no blocks named'}; "
            f"valid: {', '.join(_BLOCK_KEYS)}")
    if "dpm_batched" in keys:
        keys.add("dpm")  # dpm_batched reuses the controller dpm builds
    return frozenset(keys)

_TOOL_MODULES = {}


def _load_tool(name):
    """Load a tools/*.py module by file path (they are scripts, not a
    package) — one loader, one module object, for every bench block that
    borrows a drill (the serve `slo` block and the resilience block both
    use chaos_drill)."""
    if name not in _TOOL_MODULES:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tools", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _TOOL_MODULES[name] = mod
    return _TOOL_MODULES[name]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("auto", "sd14", "tiny", "rehearse"),
                    default="auto",
                    help="auto/sd14: the SD-1.4 measurement, which needs a "
                         "TPU and fails without one; tiny: single-group CPU "
                         "smoke; rehearse: every sd14 variant/secondary "
                         "block at tiny scale (CPU CI for the bench itself "
                         "— run with JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    return _measure("sd14" if args.preset == "auto" else args.preset)


def _measure(preset):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import Pipeline, text2image
    from p2p_tpu.models import SD14, TINY, init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    # A CPU-measured sd14 line must never be printed as a device metric:
    # the platform is checked here and embedded in every JSON line.
    platform = jax.devices()[0].platform
    if preset == "sd14" and platform != "tpu":
        print(f"sd14 measurement refused: the jax backend is {platform!r}, "
              "not tpu", file=sys.stderr)
        return 1

    t0 = time.monotonic()
    # Rehearsal disables the budget gates unconditionally (an inherited
    # P2P_BENCH_BUDGET_S must not silently re-enable skips): every block
    # must actually run.
    budget = (1e9 if preset == "rehearse"
              else float(os.environ.get("P2P_BENCH_BUDGET_S", "1800")))

    def time_left():
        return budget - (time.monotonic() - t0)

    problems = []

    def note(msg):
        # Failure/skip note: stderr, and the run exits nonzero — a run that
        # silently skips or swallows a block would pass for a whole one.
        print(msg, file=sys.stderr)
        problems.append(msg)

    # "rehearse" runs every on-accel code path (variant sweep + all
    # secondaries) at tiny scale — the CPU rehearsal of the bench itself.
    full = preset == "sd14"
    only = _secondaries_filter(preset, os.environ.get("P2P_BENCH_SECONDARIES"))
    on_accel = full or preset == "rehearse"
    cfg = SD14 if full else TINY
    num_steps = 50 if full else 4
    dtype = jnp.bfloat16 if full else jnp.float32
    self_px = 16 * 16 if full else 8 * 8
    blend_res = 16 if full else 8

    # sequential=True: collision-free ids regardless of prompt corpus — a
    # hash collision must never abort a measurement.
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length,
                            sequential=True)
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok,
    )
    prompts = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
    controller = factory.attention_replace(
        prompts, num_steps, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok,
        self_max_pixels=self_px,
        max_len=cfg.text.max_length)

    def run(seed):
        img, _, _ = text2image(pipe, prompts, controller, num_steps=num_steps,
                               rng=jax.random.PRNGKey(seed), dtype=dtype)
        return jax.block_until_ready(img)

    def timed(fn, n_runs=3):
        fn(0)  # compile
        t0 = time.perf_counter()
        for i in range(n_runs):
            fn(i + 1)
        return n_runs / (time.perf_counter() - t0)

    baseline = 4.0  # img/s/chip target (BASELINE.md north star)
    metric = (f"sd14_512_replace_edit_{num_steps}step_imgs_per_s" if full
              else ("bench_rehearsal_imgs_per_s" if on_accel
                    else "tiny_cpu_smoke_imgs_per_s"))
    best = {"value": 0.0, "variant": "single_group"}
    extras = {}

    def report():
        # Current-best line after every variant: the last JSON line is the
        # result, so a sweep can only improve the reported number, and a
        # run cut at its time limit still shows how far it got.
        print(json.dumps({
            "metric": metric,
            "value": round(best["value"], 4),
            "unit": "img/s/chip" if full else "img/s",
            "platform": platform,
            # The baseline is defined for the SD-1.4 TPU workload; a
            # tiny-model CPU rate is not comparable to it, so report
            # 0 rather than a meaningless (and flattering) ratio.
            "vs_baseline": (round(best["value"] / baseline, 4)
                            if full else 0.0),
            "variant": best["variant"],
            **extras,
        }), flush=True)

    if only is None:
        rate1 = timed(run) * len(prompts)
        best["value"] = rate1
        extras["single_group_imgs_per_s"] = round(rate1, 4)
    else:
        # A narrowed run measures ONLY the requested blocks: value 0 + the
        # marker make the line unmistakably partial. No report() yet: the
        # first JSON line must only exist once a requested block has
        # actually completed.
        best["variant"] = "narrowed"
        extras["narrowed"] = ",".join(sorted(only))
    if only is None:
        report()

    if on_accel:
        # Import failures here must degrade like any batched-variant failure
        # (keep the single-group number; skip the variants that need these).
        try:
            from p2p_tpu.engine.sampler import encode_prompts
            from p2p_tpu.parallel import seed_latents, sweep
        except Exception as e:
            note(f"batched variants unavailable ({type(e).__name__}: {e})")
            encode_prompts = seed_latents = sweep = None

        def broadcast_groups(g, ctrl):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (g,) + x.shape), ctrl)

        def run_batched(g, ctrls, seed, steps=num_steps, scheduler="ddim",
                        bpipe=None, bprompts=None, gate=None,
                        schedule=None, kernels=None):
            # Prompt encoding stays inside the timed region, matching
            # what text2image times for the single-group variant. Guidance
            # always comes from the pipe's config (sweep's 7.5 default only
            # coincidentally matches SD — LDM runs at 5.0).
            bpipe = bpipe if bpipe is not None else pipe
            bprompts = bprompts if bprompts is not None else prompts
            cond = encode_prompts(bpipe, bprompts, dtype=dtype)
            uncond = encode_prompts(bpipe, [""] * len(bprompts), dtype=dtype)
            ctx = jnp.concatenate([uncond, cond], axis=0)
            ctx = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
            lats = seed_latents(jax.random.PRNGKey(seed), g, len(bprompts),
                                bpipe.latent_shape, dtype=dtype)
            imgs, _ = sweep(bpipe, ctx, lats, ctrls, num_steps=steps,
                            scheduler=scheduler, mesh=None, gate=gate,
                            schedule=schedule, kernels=kernels,
                            guidance_scale=bpipe.config.guidance_scale)
            return jax.block_until_ready(imgs)

        # Operating-point sweep: g independent edit groups vmapped on the one
        # chip (the seed-sweep batching PERF.md documents). g=4 first, so a
        # run cut at its time limit still holds the likeliest best via the
        # best-so-far reporting (the ranking has not been measured on the
        # current code).
        # Guarded: a failure here must not discard the measurement above.
        if sweep is not None and (only is None or "gsweep" in only):
          try:
            for g in (4, 2, 8):
                # Each g is a fresh XLA program: leave room for its compile
                # plus the timed runs (~4 sampling passes) before the kill.
                if time_left() < 300:
                    note(f"g-sweep stopped before g={g}: "
                         f"{time_left():.0f}s left")
                    break
                ctrls = broadcast_groups(g, controller)
                rate = (timed(lambda s, g=g, c=ctrls: run_batched(g, c, s))
                        * g * len(prompts))
                extras[f"batched_{g}groups_imgs_per_s"] = round(rate, 4)
                if rate > best["value"]:
                    best.update(value=rate, variant=f"batched_{g}groups")
                report()
          except Exception as e:  # keep the best number so far
            note(f"batched variant failed ({type(e).__name__}: {e}); "
                 f"reporting {best['variant']}")

        def secondary(key, name, fn, min_left=300, needs_sweep=False,
                      prereq=True, prereq_msg=""):
            """One budget-gated, failure-isolated secondary measurement.

            Skip causes report distinctly (missing batched imports vs failed
            prerequisite vs time budget), and every skip or failure goes
            through note() so it fails the rehearsal. An operator-requested
            P2P_BENCH_SECONDARIES narrowing is not a problem, so it skips
            silently."""
            if only is not None and key not in only:
                return
            if needs_sweep and sweep is None:
                note(f"{name} skipped: batched imports unavailable")
            elif not prereq:
                note(f"{name} skipped: {prereq_msg}")
            elif time_left() <= min_left:
                note(f"{name} skipped: {time_left():.0f}s left")
            else:
                try:
                    fn()
                    report()
                except Exception as e:
                    note(f"{name} failed ({type(e).__name__}: {e})")

        # Phase-gated variant of the headline batched-4-groups config
        # (ISSUE 1 tentpole): gate=0.5T — phase 1 is the full CFG program
        # with controller hooks, phase 2 drops the uncond batch half and
        # serves cross-attention from the phase-1 cache. The BENCH schema
        # gains gate_step / per-phase ms/step / the phase-2 U-Net batch so
        # the trajectory distinguishes this algorithmic win from kernel
        # wins. The headline metric itself stays the exact (ungated)
        # sampler; the gated rate is an extra, like dpm20.
        def gated_variant():
            from p2p_tpu.controllers.base import controller_step_window
            from p2p_tpu.engine.sampler import resolve_gate

            g = 4
            gate_frac = 0.5  # the ISSUE 1 spec point: gate=0.5T
            gate_step = resolve_gate(gate_frac, num_steps, controller)
            # gate=0.5T cuts inside the headline controller's 0.8T cross
            # window (edits past the gate ride the cache, late-window blend
            # steps are dropped) — record the window end so the json says
            # outright that this operating point trades edit-window tail
            # for speed, rather than looking comparable to batched_4groups.
            extras["gate_window_end"] = controller_step_window(controller,
                                                               num_steps)
            ctrls = broadcast_groups(g, controller)
            imgs_per_run = g * len(prompts)
            rate = timed(lambda s, c=ctrls: run_batched(
                g, c, s, gate=gate_frac)) * imgs_per_run
            extras["batched_4groups_gate05_imgs_per_s"] = round(rate, 4)
            extras["gate_step"] = gate_step
            # Phase 2 runs the conditional half only: per-group U-Net batch
            # B (= #prompts), not 2B — recorded so the json proves the
            # smaller program shipped, not just a rate delta.
            extras["phase2_unet_batch"] = [g, len(prompts)]
            full_rate = extras.get("batched_4groups_imgs_per_s")
            if full_rate:
                # Derived phase split: every step of the ungated program is
                # a phase-1 step, so phase-1 ms/step comes from the ungated
                # rate and phase-2 ms/step is what's left of the gated
                # wall time after gate_step phase-1 steps. Cross-run noise
                # (cache warmth, host jitter) can push the subtraction
                # below zero; clamp — a 0.0 reads unambiguously as
                # "noise-dominated split", a negative number would poison
                # any trajectory analysis consuming the schema.
                t_full = imgs_per_run / full_rate
                t_gated = imgs_per_run / rate
                p1_ms = t_full / num_steps * 1000.0
                p2_steps = num_steps - gate_step
                p2_ms = (t_gated * 1000.0 - gate_step * p1_ms) / p2_steps
                extras["phase1_ms_per_step"] = round(p1_ms, 2)
                extras["phase2_ms_per_step"] = round(max(p2_ms, 0.0), 2)

            # ISSUE 15: the SEARCHED per-site reuse schedule — the
            # committed artifact (tools/schedules/default_v1.json, the
            # schedule-search winner) run on the same operating point.
            # Recorded as the nested `gate.schedule` sub-record so the
            # trajectory (and benchwatch's `gate.schedule.speedup`
            # headline, higher=better) can split the generalized-schedule
            # win from the single-gate one. The drift side of the claim is
            # the quality gate's `schedule` leg; this block records speed.
            import json as _json

            from p2p_tpu.engine.reuse import resolve_schedule
            from p2p_tpu.models.config import unet_layout as _ulayout
            from p2p_tpu.ops import schedulers as _sched_mod

            art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tools", "schedules", "default_v1.json")
            with open(art) as f:
                spec = _json.load(f)
            sched_rate = timed(lambda s, c=ctrls: run_batched(
                g, c, s, schedule=spec)) * imgs_per_run
            scan = int(_sched_mod.schedule_from_config(
                num_steps, pipe.config.scheduler,
                kind="ddim").timesteps.shape[0])
            resolved = resolve_schedule(spec, _ulayout(pipe.config.unet),
                                        scan, controller)
            sub = {
                "artifact": "tools/schedules/default_v1.json",
                "imgs_per_s": round(sched_rate, 4),
                "cfg_gate_step": resolved.cfg_gate,
                "sites_cached": resolved.sites_cached(),
                "cached_site_steps_fraction": round(
                    resolved.cached_site_steps_fraction(), 4),
                "search_speedup": (spec.get("provenance") or {}).get(
                    "measured_speedup"),
            }
            # Mean ms/step of the scheduled run — the honestly-measurable
            # per-step fact. The gate block's derived phase split does NOT
            # generalize here: its arithmetic assumes phase 1 costs the
            # ungated rate, and a schedule removes compute from phase 1
            # too (sites reused while CFG is live), so a derived split
            # would be systematically fictitious, not noisy.
            sub["ms_per_step"] = round(
                imgs_per_run / sched_rate / num_steps * 1000.0, 2)
            if full_rate:
                # Speedup over the UNGATED baseline at the same operating
                # point — the ISSUE 15 ≥1.5× target — plus the single-gate
                # ladder rung for the PERF.md ladder.
                sub["speedup"] = round(sched_rate / full_rate, 4)
                sub["uniform_gate_speedup"] = round(rate / full_rate, 4)
            extras["gate"] = {"schedule": sub}

        # ISSUE 16: the fused in-kernel-edit attention A/B on the headline
        # operating point — fused (`kernels=KernelConfig`) vs the
        # materialized reference (the batched_4groups headline itself: same
        # controller, kernels=None) vs the library-flash floor (no
        # controller: what the step costs with zero edit overhead — the
        # ceiling the fused path closes toward). Recorded as the nested
        # `gate.kernel` sub-record with per-variant ms/step and MFU (each
        # variant's own XLA cost-card flops over its measured wall time);
        # benchwatch reads `gate.kernel.speedup` (fused over materialized,
        # higher is better). On CPU the kernels run through the pallas
        # INTERPRETER — a correctness/schema rehearsal whose ms/step is
        # recorded honestly but means nothing for speed (the interpreter
        # is a Python loop); `interpret: true` marks those rounds so the
        # trajectory never mistakes a rehearsal number for a chip number.
        def kernel_variant():
            from p2p_tpu.kernels import (VARIANT_FUSED, KernelConfig,
                                         site_variant)
            from p2p_tpu.models.config import unet_layout as _ulayout
            from p2p_tpu.obs import costmodel

            g = 4
            interp = platform != "tpu"
            kc = KernelConfig(interpret=True) if interp else KernelConfig()
            ctrls = broadcast_groups(g, controller)
            imgs_per_run = g * len(prompts)
            full_rate = extras["batched_4groups_imgs_per_s"]

            # Static census at the operating point: how many sites the
            # config actually lowers fused (store-slot sites under this
            # store-carrying controller stay materialized by design).
            layout = _ulayout(cfg.unet)
            fused_sites = sum(
                1 for m in layout.metas
                if site_variant(kc, controller, m, "off") == VARIANT_FUSED)

            fused_rate = timed(lambda s, c=ctrls: run_batched(
                g, c, s, kernels=kc)) * imgs_per_run
            flash_rate = timed(lambda s: run_batched(
                g, None, s)) * imgs_per_run

            def ms_per_step(rate):
                return imgs_per_run / rate / num_steps * 1000.0

            sub = {
                "fused_imgs_per_s": round(fused_rate, 4),
                "fused_ms_per_step": round(ms_per_step(fused_rate), 2),
                "materialized_ms_per_step": round(ms_per_step(full_rate), 2),
                "flash_ms_per_step": round(ms_per_step(flash_rate), 2),
                "speedup": round(fused_rate / full_rate, 4),
                "fused_sites": fused_sites,
                "interpret": interp,
            }
            # Per-variant MFU off each variant's own cost card: the fused
            # program's flops/bytes genuinely differ (no materialized
            # probs), so one shared card would misattribute.
            peaks = costmodel.detect_peaks()
            cond = encode_prompts(pipe, prompts, dtype=dtype)
            uncond = encode_prompts(pipe, [""] * len(prompts), dtype=dtype)
            ctx = jnp.concatenate([uncond, cond], axis=0)
            ctx = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
            lats = seed_latents(jax.random.PRNGKey(0), g, len(prompts),
                                pipe.latent_shape, dtype=dtype)
            for name, c, kk, rate in (
                    ("fused", ctrls, kc, fused_rate),
                    ("materialized", ctrls, None, full_rate),
                    ("flash", None, None, flash_rate)):
                lowered = sweep(pipe, ctx, lats, c, num_steps=num_steps,
                                scheduler="ddim", mesh=None, kernels=kk,
                                guidance_scale=pipe.config.guidance_scale,
                                lower_only=True)
                card = costmodel.card_from_compiled(
                    lowered.compile(), program=f"kernel/{name}")
                mfu = costmodel.mfu_pct(card.flops,
                                        imgs_per_run / rate * 1000.0, peaks)
                sub[f"{name}_mfu_pct"] = (None if mfu is None
                                          else round(mfu, 2))
            extras.setdefault("gate", {})["kernel"] = sub

        # Quality-matched secondary: DPM-Solver++(2M) at 20 steps reaches
        # ~50-step-DDIM quality (PERF.md) — the practical operating point.
        dpm_ctrl = {}

        def dpm_single():
            ctrl = factory.attention_replace(
                prompts, 20, cross_replace_steps=0.8,
                self_replace_steps=0.4, tokenizer=tok,
                self_max_pixels=self_px, max_len=cfg.text.max_length)

            def run_dpm(seed):
                img, _, _ = text2image(
                    pipe, prompts, ctrl, num_steps=20, scheduler="dpm",
                    rng=jax.random.PRNGKey(seed), dtype=dtype)
                return jax.block_until_ready(img)

            extras["dpm20_imgs_per_s"] = round(timed(run_dpm) * len(prompts), 4)
            dpm_ctrl["ctrl"] = ctrl

        # DPM at batched operating points: the highest practical
        # quality-matched rate the chip reaches. g=8 first, then g=4: the
        # optimum may be below 8; measure rather than assume. Secondary
        # extras only —
        # the headline metric stays the spec'd 50-step DDIM workload.
        def dpm_batched():
            for g in (8, 4):
                if time_left() <= 300:
                    # Each g is a fresh XLA program; never start a compile
                    # that can't finish (~300s threshold, mirroring the DDIM
                    # sweep's guard). Checked at the top of the loop: the
                    # old between-g check could still launch g=8 into a
                    # near-empty budget and eat the kill there.
                    note(f"dpm batched g={g} skipped: "
                         f"{time_left():.0f}s left")
                    break
                ctrls_g = broadcast_groups(g, dpm_ctrl["ctrl"])
                rate = timed(lambda s, g=g, c=ctrls_g: run_batched(
                    g, c, s, steps=20, scheduler="dpm")) * g * len(prompts)
                extras[f"dpm20_batched_{g}groups_imgs_per_s"] = round(rate, 4)
                # Best-so-far after every variant: a timeout kill during the
                # next g must not lose this one (same contract as the DDIM
                # g-sweep).
                report()

        # BASELINE config 3: AttentionReweight equalizer sweep — 4 groups
        # with per-group equalizer scales riding ONE compiled program (the
        # scales are traced leaves; `/root/reference/main.py:281-290` is a
        # batch on one device, here it's the dp sweep engine).
        def reweight_eqsweep():
            from p2p_tpu.align.words import get_equalizer

            rw_prompts = [prompts[0], prompts[0]]
            rw_list = []
            for scale in (0.5, 1.0, 2.0, 4.0):
                eq = get_equalizer(rw_prompts[1], ("burger",), (scale,), tok)
                rw_list.append(factory.attention_reweight(
                    rw_prompts, num_steps, cross_replace_steps=0.8,
                    self_replace_steps=0.4, equalizer=eq, tokenizer=tok,
                    self_max_pixels=self_px, max_len=cfg.text.max_length))
            rw_ctrls = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *rw_list)
            g = 4
            rate = timed(lambda s: run_batched(
                g, rw_ctrls, s, bprompts=rw_prompts)) * g * len(rw_prompts)
            extras["reweight_eqsweep_4groups_imgs_per_s"] = round(rate, 4)

        # BASELINE config 2: AttentionRefine + LocalBlend, 2 prompts, 50
        # steps. A different controller structure (NW gather + blend step
        # callback reading the store) → a distinct XLA program from the
        # headline Replace edit.
        def refine_localblend():
            rb_prompts = ["a squirrel eating a burger",
                          "a squirrel eating a tasty burger"]
            blend = factory.local_blend(
                rb_prompts, ("burger", "burger"), tok, start_blend=0.2,
                num_steps=num_steps, resolution=blend_res,
                max_len=cfg.text.max_length)
            ctrl_rb = factory.attention_refine(
                rb_prompts, num_steps, cross_replace_steps=0.8,
                self_replace_steps=0.4, tokenizer=tok, local_blend=blend,
                self_max_pixels=self_px, max_len=cfg.text.max_length)

            def run_rb(seed):
                img, _, _ = text2image(
                    pipe, rb_prompts, ctrl_rb, num_steps=num_steps,
                    rng=jax.random.PRNGKey(seed), dtype=dtype)
                return jax.block_until_ready(img)

            extras["refine_localblend_imgs_per_s"] = round(
                timed(run_rb) * len(rb_prompts), 4)

        # BASELINE config 5: the LDM-256 backend (BERT-style text tower, VQ
        # decode, β 0.0015..0.0195), 8-prompt batch = 4 edit groups of 2
        # through the dp sweep engine.
        def ldm256_batch():
            from p2p_tpu.models.config import LDM256, TINY_LDM

            ldm_cfg = LDM256 if full else TINY_LDM
            ltok = HashWordTokenizer(
                model_max_length=ldm_cfg.text.max_length, sequential=True)
            lpipe = Pipeline(
                config=ldm_cfg,
                unet_params=init_unet(jax.random.PRNGKey(10), ldm_cfg.unet),
                text_params=init_text_encoder(jax.random.PRNGKey(11),
                                              ldm_cfg.text),
                vae_params=vae_mod.init_vae(jax.random.PRNGKey(12),
                                            ldm_cfg.vae),
                tokenizer=ltok)
            lctrl = factory.attention_replace(
                prompts, num_steps, cross_replace_steps=0.8,
                self_replace_steps=0.4, tokenizer=ltok,
                self_max_pixels=self_px, max_len=ldm_cfg.text.max_length)
            g = 4
            lctrls = broadcast_groups(g, lctrl)
            rate = timed(lambda s: run_batched(
                g, lctrls, s, bpipe=lpipe)) * g * len(prompts)
            extras["ldm256_8prompt_imgs_per_s"] = round(rate, 4)

        # Request-level serving rehearsal (ISSUE 2): replay a deterministic
        # loadgen Poisson trace through the serve loop (queue → dynamic
        # batcher → program cache → sweep) and record the serving schema —
        # p50/p95 request latency, mean batch occupancy, program-cache hit
        # rate — so future rounds track serving regressions alongside raw
        # throughput. Compile-ahead (prewarm) keeps the one program build
        # off the request path, exactly as the serve CLI defaults to; the
        # trace is sized so the batcher runs at steady occupancy (arrivals
        # far denser than a batch's service time).
        def serve_rehearsal():
            from p2p_tpu.serve import Request, serve_forever

            loadgen = _load_tool("loadgen")

            n = 16 if full else 24
            trace_dicts = loadgen.generate_trace(
                n, mode="poisson", rate_per_s=50.0, seed=0,
                steps=num_steps)
            reqs = [Request.from_dict(d) for d in trace_dicts]
            summary = None
            n_ok = 0
            for rec in serve_forever(pipe, reqs, max_batch=4,
                                     max_wait_ms=100.0,
                                     prewarm=reqs[:1]):
                if rec["status"] == "ok":
                    n_ok += 1
                elif rec["status"] == "summary":
                    summary = rec
            if n_ok != n:
                raise RuntimeError(
                    f"serve rehearsal served {n_ok}/{n} requests "
                    f"(counts: {summary and summary['counts']})")
            extras["serve"] = {
                "n_requests": n,
                "n_batches": summary["n_batches"],
                "p50_ms": round(summary["p50_ms"], 2),
                "p95_ms": round(summary["p95_ms"], 2),
                "mean_batch_occupancy": round(
                    summary["mean_batch_occupancy"], 3),
                "program_cache_hit_rate": round(
                    summary["dispatch_hit_rate"], 4),
                "prewarm_ms": round(summary["prewarm_ms"], 1),
            }

            # Phase-disaggregated A/B (ISSUE 6): the SAME gate-mix trace
            # through the single-pool baseline (phase_pools=False — the
            # pre-disaggregation engine) and the two-pool engine, each
            # after a warmup pass so both sides run warm programs. The
            # sub-record captures the architectural facts (hand-off rate,
            # per-phase occupancy, phase-2 pack width at the doubled
            # equal-footprint cap) plus the measured throughput/p95
            # comparison. On a linear-batch-cost CPU host the wall-clock
            # ratio sits near 1.0 (equal total compute repacked); the
            # width-restoration win — phase 2 running 2x the lanes at the
            # CFG phase's device batch — is what a chip run quantifies from
            # these same keys.
            mix = loadgen.parse_gate_mix("0.5:3,off:1")
            n2 = 12 if full else 24
            trace2 = loadgen.generate_trace(
                n2, mode="poisson", rate_per_s=50.0, seed=1,
                steps=num_steps, gate_mix=mix)
            reqs2 = [Request.from_dict(d) for d in trace2]
            pre2 = ([r for r in reqs2 if r.gate is not None][:1]
                    + [r for r in reqs2 if r.gate is None][:1])

            def run_ab(pools):
                s = None
                ok = 0
                for rec in serve_forever(pipe,
                                         [Request.from_dict(d)
                                          for d in trace2],
                                         max_batch=4, max_wait_ms=100.0,
                                         prewarm=pre2, phase_pools=pools):
                    if rec["status"] == "ok":
                        ok += 1
                    elif rec["status"] == "summary":
                        s = rec
                if ok != n2:
                    raise RuntimeError(
                        f"serve A/B ({'two' if pools else 'single'}-pool) "
                        f"served {ok}/{n2} (counts: {s and s['counts']})")
                return s

            run_ab(False)                     # warm both paths' programs
            run_ab(True)
            s_single = run_ab(False)
            s_two = run_ab(True)
            ph = s_two["phases"]
            makespan_s = s_two["makespan_ms"] / 1000.0
            extras["serve"]["phases"] = {
                "n_requests": n2,
                "handoffs": ph["handoffs"],
                "handoffs_per_s": round(ph["handoffs"] / makespan_s, 3),
                "phase1_batches": ph["phase1"]["batches"],
                "phase2_batches": ph["phase2"]["batches"],
                "phase1_mean_occupancy": round(
                    ph["phase1"]["mean_occupancy"], 3),
                "phase2_mean_occupancy": round(
                    ph["phase2"]["mean_occupancy"], 3),
                "phase2_pack_p50": ph["phase2"]["pack_p50"],
                "phase2_max_batch": ph["phase2_max_batch"],
                "single_pool_makespan_ms": round(
                    s_single["makespan_ms"], 1),
                "two_pool_makespan_ms": round(s_two["makespan_ms"], 1),
                "throughput_ratio": round(
                    s_single["makespan_ms"] / s_two["makespan_ms"], 3),
                "single_pool_p95_ms": round(s_single["p95_ms"], 2),
                "two_pool_p95_ms": round(s_two["p95_ms"], 2),
            }

            # Mesh-parallel serving (ISSUE 10): the same two-pool engine
            # sharded over a dp device mesh, with loadgen driving 10x the
            # Poisson rate so the wider buckets actually fill. The devices
            # axis records how many chips the serve batch dimension spans;
            # dp=1 vs dp=N makespans give the scaling ratio and the
            # per-device img/s — the on-chip near-linear-scaling claim is
            # what a chip run measures from these same keys
            # (a linear-batch-cost CPU host repacks equal compute, so the
            # rehearsal ratio sits near 1.0, exactly like the phases A/B).
            from p2p_tpu.serve import MeshSpec

            ndev = len(jax.devices())
            dp = 1
            while dp * 2 <= min(ndev, 4):
                dp *= 2
            n4 = 12 if full else 24
            trace4 = loadgen.generate_trace(
                n4, mode="poisson", rate_per_s=500.0, seed=2,
                steps=num_steps, gate_mix=mix)
            pre4_r = [Request.from_dict(d) for d in trace4]
            pre4 = ([r for r in pre4_r if r.gate is not None][:1]
                    + [r for r in pre4_r if r.gate is None][:1])

            def run_mesh(spec):
                s = None
                ok = imgs = 0
                for rec in serve_forever(pipe,
                                         [Request.from_dict(d)
                                          for d in trace4],
                                         max_batch=2, max_wait_ms=100.0,
                                         prewarm=pre4, mesh=spec):
                    if rec["status"] == "ok":
                        ok += 1
                        imgs += len(rec["images"])
                    elif rec["status"] == "summary":
                        s = rec
                if ok != n4:
                    raise RuntimeError(
                        f"serve mesh leg (dp={spec.dp}) served {ok}/{n4} "
                        f"(counts: {s and s['counts']})")
                return s, imgs

            run_mesh(MeshSpec(dp=1))            # warm both mesh shapes'
            run_mesh(MeshSpec(dp=dp))           # programs before timing
            s_dp1, _ = run_mesh(MeshSpec(dp=1))
            s_mesh, imgs_mesh = run_mesh(MeshSpec(dp=dp))
            mesh_s = s_mesh["makespan_ms"] / 1000.0
            phm = s_mesh["phases"]
            extras["serve"]["mesh"] = {
                "devices": dp,
                "n_requests": n4,
                "dp1_makespan_ms": round(s_dp1["makespan_ms"], 1),
                "mesh_makespan_ms": round(s_mesh["makespan_ms"], 1),
                "scaling_ratio": round(
                    s_dp1["makespan_ms"] / s_mesh["makespan_ms"], 3),
                "imgs_per_s_per_device": round(imgs_mesh / mesh_s / dp, 4),
                "phase2_pack_p50": phm["phase2"]["pack_p50"],
                "phase2_max_batch": phm["phase2_max_batch"],
                "handoffs": phm["handoffs"],
            }

            # SLO-tiered overload protection (ISSUE 12): the seeded
            # tenant/tier-mixed 2x-overload drill on the deterministic
            # virtual clock (tools/chaos_drill.slo_overload_drill, the
            # same scenario the quality gate's `slo` check enforces).
            # The headline key is premium_p99_ratio — premium p99 under
            # the overload over its uncontended p99 (bound 1.2x, watched
            # by tools/benchwatch.py, direction: lower is better); the
            # shed split records that best-effort absorbed the overload.
            # All control-flow facts on an injected clock, so the
            # sub-record is byte-stable across rounds and hosts.
            extras["serve"]["slo"] = _load_tool(
                "chaos_drill").slo_overload_drill(pipe)

            # Semantic caching (ISSUE 13): the seeded --zipf 1.1 cached-
            # vs-uncached parity drill (tools/chaos_drill.py, the same
            # scenario the quality gate's `cache_parity` leg enforces —
            # every cached serve bitwise-identical to its uncached twin).
            # The headline key is amplification: img/s served cached over
            # uncached at the identical offered trace — equal device-
            # seconds of demand, so unlike repacking wins this one is
            # honestly measurable at CPU rehearsal (served-from-cache
            # requests cost no compute on ANY backend). Watched by
            # tools/benchwatch.py (serve.cache.amplification, higher is
            # better) alongside the per-layer hit rates.
            extras["serve"]["cache"] = _load_tool(
                "chaos_drill").cache_parity_drill(pipe)

            # Production profiling (ISSUE 18): re-serve the headline
            # rehearsal trace with a ProdScope attached — sampled device
            # captures into a bounded trace ring, folded into the
            # workload-profile ledger — and record what it observed and
            # what it cost. overhead_pct is capture wall time over
            # non-capture serve wall time as the profiler itself
            # accounts it: honest but scale-dependent. At CPU-rehearsal
            # dispatch durations the trace start/stop + parse dominates,
            # so the number sits far above what 1/N sampling costs on
            # multi-second device dispatches — the benchwatch trend
            # (serve.profile.overhead_pct, lower is better) is the
            # regression signal, not the absolute value.
            import tempfile

            from p2p_tpu.obs.prodscope import ProdScope

            with tempfile.TemporaryDirectory() as ptmp:
                scope = ProdScope(os.path.join(ptmp, "profile"),
                                  seed=0, period=4,
                                  tags={"preset": "tiny",
                                        "bench": "serve_rehearsal"})
                reqs_p = [Request.from_dict(d) for d in trace_dicts]
                ok_p = 0
                s_prof = None
                for rec in serve_forever(pipe, reqs_p, max_batch=4,
                                         max_wait_ms=100.0,
                                         prewarm=reqs_p[:1],
                                         prodscope=scope):
                    if rec["status"] == "ok":
                        ok_p += 1
                    elif rec["status"] == "summary":
                        s_prof = rec
                if ok_p != n:
                    raise RuntimeError(
                        f"serve profile leg served {ok_p}/{n} "
                        f"(counts: {s_prof and s_prof['counts']})")
                prof = s_prof["profile"]
                extras["serve"]["profile"] = {
                    "captures": prof["captures"],
                    "sampled_1_in": 4,
                    "sites_measured": prof["sites_measured"],
                    "ledger_bytes": prof["ledger_bytes"],
                    "overhead_pct": round(prof["overhead_pct"], 1),
                    "drift_events": prof["drift_events"],
                }

            # Elastic mesh serving (ISSUE 19): the three-leg elastic drill
            # (tools/chaos_drill.elastic_resize_drill, the same scenario
            # the quality gate's `elastic` check enforces) — a seeded
            # diurnal pressure trace the engine must ride by resizing dp
            # up AND down with zero drops, fixed-topology parity within
            # the documented vmap tolerance (±1 uint8 step), and a
            # mid-resize kill that must restart on the WAL-recorded
            # target topology and resume every parked carry off its
            # spill, exactly-once. The headline key is
            # cutover_pause_p95_ms — how long in-flight phase-2 work sat
            # parked across a cutover (watched by tools/benchwatch.py,
            # lower is better); the drill runs real runners on its
            # deterministic virtual clock, so the sub-record is
            # byte-stable across rounds and hosts. Needs >= 4 devices
            # for the 1<->2<->4 dp swing (the rehearsal inherits the
            # virtual 8-device CPU platform; a bare host without a mesh
            # simply omits the sub-record, like a narrowed secondary).
            if len(jax.devices()) >= 4:
                with tempfile.TemporaryDirectory() as etmp:
                    extras["serve"]["elastic"] = _load_tool(
                        "chaos_drill").elastic_resize_drill(
                            pipe, os.path.join(etmp, "elastic.wal"))

        # Telemetry-overhead block (ISSUE 3): the same headline single-group
        # edit run with the obs instrumentation enabled (phase-tagged step
        # callbacks traced in, host collector installed) vs disabled, so
        # every BENCH round records what the instrumented path costs — the
        # bound the quality gate's obs_overhead check enforces, measured on
        # the round's own hardware. step_events doubles as a liveness
        # check: 0 means the callback channel was silently mis-wired.
        def obs_overhead():
            from p2p_tpu.obs import device as obs_device
            from p2p_tpu.obs import metrics as obs_metrics

            def run_m(seed, m):
                img, _, _ = text2image(
                    pipe, prompts, controller, num_steps=num_steps,
                    rng=jax.random.PRNGKey(seed), dtype=dtype, metrics=m)
                return jax.block_until_ready(img)

            run_m(0, False)   # warm both programs before timing
            run_m(0, True)
            n_runs = 2
            t0 = time.perf_counter()
            for i in range(n_runs):
                run_m(i + 1, False)
            t_off = (time.perf_counter() - t0) / n_runs
            obs_metrics.registry().reset()
            with obs_device.instrument():
                t0 = time.perf_counter()
                for i in range(n_runs):
                    run_m(i + 1, True)
                t_on = (time.perf_counter() - t0) / n_runs
            snap = obs_metrics.registry().snapshot()
            steps_seen = sum(
                s["value"] for s in snap.get("sampler_steps_total",
                                             {"samples": []})["samples"])
            extras["obs"] = {
                "disabled_s_per_run": round(t_off, 4),
                "enabled_s_per_run": round(t_on, 4),
                "overhead_pct": round(max(0.0, t_on / t_off - 1.0) * 100, 2),
                "step_events": int(steps_seen),
            }

        # Cost-observatory block (ISSUE 14): the tool-derived form of the
        # PERF.md headline arithmetic, measured per round on the round's
        # own hardware. The U-Net step program at the headline CFG batch
        # (the unit prof_breakdown and the 40.75 ms/step verdict measure)
        # gets an XLA cost card (obs/costmodel.py: flops, bytes accessed,
        # roofline verdict, model-predicted ms vs the platform peaks —
        # datasheet on chip, calibrated microbenchmarks at rehearsal) and
        # a measured scan timing, so the BENCH schema carries
        # step_mfu_pct as a benchwatch headline (higher is better) — a
        # regression that wastes the chip shows up as a number, not as
        # prose in PERF.md.
        def cost_observatory():
            from p2p_tpu.models import unet_layout
            from p2p_tpu.models.unet import apply_unet
            from p2p_tpu.obs import costmodel

            layout = unet_layout(cfg.unet)
            b_unet = 2 * len(prompts)          # CFG-doubled U-Net batch
            s = cfg.latent_size
            x = jnp.ones((b_unet, s, s, cfg.unet.in_channels), dtype)
            ctx_b = jnp.ones((b_unet, cfg.unet.context_len,
                              cfg.unet.context_dim), dtype)
            single = jax.jit(lambda p, x, c: apply_unet(
                p, cfg.unet, x, jnp.int32(1), c, layout=layout)[0])
            card = costmodel.card_from_compiled(
                single.lower(pipe.unet_params, x, ctx_b).compile(),
                program=f"unet_step_b{b_unet}")

            @jax.jit
            def unet_scan(p, x, c):
                def body(h, t):
                    eps, _ = apply_unet(p, cfg.unet, h, t, c,
                                        layout=layout)
                    return eps, None
                out, _ = jax.lax.scan(
                    body, x, jnp.arange(num_steps, dtype=jnp.int32))
                return out

            jax.block_until_ready(
                unet_scan(pipe.unet_params, x, ctx_b))  # compile
            best_s = min(
                costmodel._timed(lambda: jax.block_until_ready(
                    unet_scan(pipe.unet_params, x, ctx_b)))
                for _ in range(2))
            ms_per_step = best_s / num_steps * 1000.0
            peaks = costmodel.detect_peaks()
            roof = costmodel.roofline(card.flops, card.bytes_accessed,
                                      peaks)
            mfu = costmodel.mfu_pct(card.flops, ms_per_step, peaks)
            extras["cost"] = {
                "program": card.program,
                "unet_batch": b_unet,
                "flops_per_step": card.flops,
                "bytes_per_step": card.bytes_accessed,
                "arith_intensity": round(roof["arith_intensity"], 3),
                "roofline": roof["bound"],
                "predicted_ms_per_step": round(roof["predicted_ms"], 3),
                "measured_ms_per_step": round(ms_per_step, 3),
                "peak_flops_per_s": peaks.flops_per_s,
                "peak_bytes_per_s": peaks.bytes_per_s,
                "peak_source": peaks.source,
                "platform": platform,
            }
            if mfu is not None:
                # Absent (n/a to benchwatch), never 0.0: a backend with
                # no cost analysis is a measurement gap, not the worst
                # possible value of a higher-is-better headline.
                extras["cost"]["step_mfu_pct"] = round(mfu, 2)

        # Resilience block (ISSUE 4): the standard seeded chaos drill
        # (tools/chaos_drill.py) through this preset's pipeline — clean run,
        # faulted run under the seed-8 fault plan, and a simulated
        # crash + journaled restart — recording what fault tolerance costs
        # per round: retry/shed counts, how much work the WAL replay
        # recovered, and the p95 latency delta the retry/backoff machinery
        # adds over the fault-free run (warmup pass first, so the delta is
        # retry cost, not compile noise). run_drill itself asserts the
        # drill invariants (exactly-once terminals, ok outputs bitwise-
        # identical to fault-free), so a resilience regression fails the
        # rehearsal rather than just skewing a number.
        def resilience_drill():
            drill = _load_tool("chaos_drill")

            # Full scale serves the trace four times: keep it small there,
            # standard-drill-sized everywhere else (matching quality_gate's
            # fault_drill numbers).
            trace, plan = drill.standard_trace(
                n=12 if full else 24, steps=num_steps if full else 4)
            res = drill.run_drill(pipe, trace, plan, crash_after=8,
                                  warmup=True)
            replay = res["crash_replay"]
            extras["resilience"] = {
                "n_requests": res["n_requests"],
                "faults_planned": res["faults_planned"],
                "faults_fired": sum(res["faults"].values()),
                "retries": res["retries"],
                "shed": res["shed"],
                "watchdog_timeouts": res["watchdog_timeouts"],
                "bitwise_compared": res["bitwise_compared"],
                "replayed_pending": replay["replayed_pending"],
                "replay_skipped_corrupt": replay["skipped_corrupt"],
                "p95_clean_ms": round(res["p95_clean_ms"], 2),
                "p95_faulted_ms": round(res["p95_faulted_ms"], 2),
                "p95_delta_ms": round(res["p95_delta_ms"], 2),
            }

        # Null-text inversion wallclock (BASELINE.json config 4 and part of
        # its metric line; `/root/reference/null_text.py:608-618` workload:
        # 50 DDIM inversion steps + per-step uncond optimization, ≤10 inner
        # Adam steps, reference lr/early-stop). One timed pass after the
        # compile pass — a wallclock metric, not a throughput sweep. Runs
        # last: its two fresh programs are the most expensive compile in the
        # bench, and a timeout kill here can no longer lose earlier extras.
        def null_inversion():
            from p2p_tpu.engine.inversion import invert

            side = cfg.image_size
            img_in = np.random.RandomState(0).randint(
                0, 256, (side, side, 3)).astype(np.uint8)

            def run_invert():
                art = invert(pipe, img_in, prompts[0],
                             num_steps=num_steps, dtype=dtype)
                return jax.block_until_ready(art.uncond_embeddings)

            run_invert()  # compile (ddim-invert + null-optimize programs)
            t1 = time.perf_counter()
            run_invert()
            extras["nullinv_s_per_image"] = round(time.perf_counter() - t1, 2)

        secondary("gate", "phase-gate secondary", gated_variant,
                  needs_sweep=True)
        # min_left=420: three extra sweep-scale programs (fused, flash
        # floor, plus the lower_only cost cards) compile here.
        secondary("kernel", "fused-kernel secondary", kernel_variant,
                  needs_sweep=True, min_left=420,
                  prereq="batched_4groups_imgs_per_s" in extras,
                  prereq_msg="no batched_4groups baseline to compare "
                             "against")
        secondary("dpm", "dpm secondary", dpm_single)
        secondary("dpm_batched", "dpm batched secondary", dpm_batched,
                  needs_sweep=True, prereq="ctrl" in dpm_ctrl,
                  prereq_msg="single-group dpm did not succeed")
        secondary("reweight", "reweight sweep secondary", reweight_eqsweep,
                  needs_sweep=True)
        secondary("refine_blend", "refine+blend secondary", refine_localblend)
        secondary("ldm256", "ldm256 secondary", ldm256_batch, needs_sweep=True)
        secondary("serve", "serve rehearsal secondary", serve_rehearsal,
                  needs_sweep=True)
        secondary("obs", "obs overhead secondary", obs_overhead)
        # min_left=420: at full scale the num_steps scan is a fresh XLA
        # program (warm persistent cache makes it disk I/O; a cold cache
        # needs the compile time nullinv also reserves).
        secondary("cost", "cost observatory secondary", cost_observatory,
                  min_left=420)
        secondary("resilience", "resilience drill secondary",
                  resilience_drill, needs_sweep=True)
        # min_left=420: the warm-cache need is two sampling-scale passes.
        # A cold-cache full run may still be cut at its time limit here,
        # but nullinv runs last so that cannot lose earlier extras — and a
        # narrowed run (P2P_BENCH_SECONDARIES=nullinv) gives the two
        # inversion programs nearly the whole budget, so even a cold
        # compile fits.
        secondary("nullinv", "null-inversion secondary", null_inversion,
                  min_left=420)

    if problems:
        print(f"BENCH INCOMPLETE ({len(problems)} block(s)): "
              + " | ".join(problems), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
