"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of SD-1.4 (``--preset sd14``: the 860 M-parameter U-Net at 512²,
the CLIP-L text encoder, the VAE; random weights from fixed PRNG keys, the
``HashWordTokenizer``), in ONE process that holds the TPU. There is no CPU
path in this script: without a TPU it exits non-zero and prints no result.

Phases, in the order they run, each printing one JSON line as it ends. A
phase that fails prints its line with ``"failed"``, the later phases still
run, and the script exits non-zero without the result line:

``edit``     ``p2p_tpu.cli.main(["edit", ...])`` — a 2-prompt AttentionReplace
             edit, 50 DDIM steps, 2 seeds; the image files exist, are not
             blank and the edited image differs from the baseline.
``kernels``  the same edit through the Pallas kernels
             (``kernels=KernelConfig()``) against the materialized run, bf16:
             fused sites > 0 in the plan; each site geometry's kernel agrees
             with ``edit_attention_reference`` on the hardware; the compiled
             program's ``tpu_custom_call`` count equals the plan's fused +
             flash site count (a kernel that gave way to the reference
             cannot pass as a kernel run); and the final latents agree
             within the documented 1e-2 latent-MSE budget, taken relative
             to the latents' mean square.
``serve``    ``tools/loadgen.py`` writes an 8-request trace (gate mix
             ``0.5:1,off:1``; gated replace and ungated refine edits);
             ``cli.main(["serve", ...])`` must end 8 records, all ``ok``,
             with their images (the CLI itself returns 0 whatever the
             records say, so the results file is read here).
``gate``     one ``--gate 0.5`` edit (phase-1/phase-2 programs).
``invert``   ``invert`` then ``replay`` at reduced step counts — the only
             path that differentiates through the flash kernel.

The run is compile-bound (nine SD-1.4 programs at 60-125 s each, cold)
and the driver allows 1200 s, so ``invert`` starts only if 600 s are left
and prints ``"skipped": "budget"`` otherwise; ``gate`` does the same below
420 s, which a host reaches that compiles a third slower than the usual one.

``--chips 4`` runs ONLY the mesh phase and what it is compared with: the
8-request trace through ``serve --mesh dp=4`` and ``--mesh dp=1``, results
compared image by image, plus a check that all four devices hold the
weights and did work. The driver runs one chip; a builder runs this by hand.

The last line of standard output is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import importlib.util
import json
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

PRESET = "sd14"
SOURCE = "a squirrel eating a burger"
TARGET = "a squirrel eating a lasagna"
EDIT_STEPS = 50
EDIT_SEEDS = (8191, 8192)
#: Width is what must be full; depth in steps is cut where compile time, not
#: step time, fills the budget (each phase line says what it ran).
GATE_STEPS = 20
SERVE_STEPS = 20
INVERT_STEPS = 10
INVERT_INNER_STEPS = 2
N_REQUESTS = 8
GATE_MIX = "0.5:1,off:1"
#: Lanes per device: the burst is served as it arrives, a few requests of one
#: kind at a time, so wider programs would mostly run on padding.
#: The mesh phase runs one lane per device at dp=4 and at dp=1, so both
#: compile the same per-device program.
SERVE_MAX_BATCH = 2
MESH_MAX_BATCH = 1
#: The documented drift budget of the fused kernels: latent MSE vs the
#: materialized reference (kernels/fused_edit.py, README "≤1e-2 latent MSE"),
#: written for latents of unit scale. Random weights leave the SD-1.4
#: latents at a standard deviation of about 15 after 50 steps, so the MSE is
#: taken relative to the materialized run's mean square.
LATENT_MSE_BUDGET = 1e-2
#: One kernel call against ``edit_attention_reference`` on the same random
#: q/k/v: max|Δ| relative to the reference's max (bf16 rounds at 4e-3).
SITE_REL_BUDGET = 1e-2
#: tests/test_golden.py's tolerance between two correct runs whose float
#: accumulation order differs (here: dp=1 and dp=4 bucket widths).
IMAGE_MAX_ABS, IMAGE_MEAN_ABS = 3, 0.5
#: The driver's limit is 1200 s. ``invert`` compiles four more SD-1.4
#: programs; it starts only if this much of the budget is left.
TIME_LIMIT_S = 1200.0
INVERT_NEEDS_S = 600.0
#: ``gate`` compiles two gated SD-1.4 programs: 254-283 s cold in three runs
#: on the chip, with 520 s left when it started. A fourth run's host compiled
#: a third slower and would have had 400 s left: there ``gate`` gives way,
#: saying so, because it could end past the limit.
GATE_NEEDS_S = 420.0

_T0 = time.monotonic()


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the backend
    compiles of a second or more (one per program, a read of the persistent
    cache included) — a reader of the program's own compile ledger
    (``utils.cache.CompileLedger``), which listens to ``jax.monitoring``, so
    the CLI's own compiles are counted too."""

    _KINDS = ("trace", "lower", "backend", "cache_hit")

    def __init__(self):
        from p2p_tpu.utils.cache import compile_ledger

        self._ledger = compile_ledger()
        self._since = time.monotonic()

    @property
    def seconds(self) -> float:
        return sum(r.seconds
                   for r in self._ledger.rows(*self._KINDS, since=self._since))

    @property
    def programs(self) -> list:
        """Backend-compile seconds of a second or more, in order."""
        return [round(r.seconds, 1)
                for r in self._ledger.rows("backend", "cache_hit",
                                           since=self._since)
                if r.seconds >= 1.0]

    def mark(self):
        return self.seconds, len(self.programs)


class Phase:
    """``with Phase("edit", clock) as ph: ...; ph.note(k=v)`` prints the
    phase's JSON line when the block ends. A phase that raises prints its
    line with ``"failed"``, the run goes on to the next phase (one call on
    the chip then shows every fault, not the first), and ``main`` exits
    non-zero without the result line."""

    failed: list = []

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock, self.fields = name, clock, {}

    def note(self, **fields):
        self.fields.update(fields)

    def __enter__(self):
        self.t0 = time.monotonic()
        self.c0, self.p0 = self.clock.mark()
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax

        if exc_type is not None:
            if not issubclass(exc_type, Exception):
                return False
            traceback.print_exception(exc_type, exc, tb)
            Phase.failed.append(self.name)
            self.fields["failed"] = f"{exc_type.__name__}: {exc}"[:2000]
        c1, p1 = self.clock.mark()
        emit(phase=self.name,
             seconds=round(time.monotonic() - self.t0, 3),
             compile_seconds=round(c1 - self.c0, 3),
             programs_compiled=p1 - self.p0,
             program_compile_seconds=self.clock.programs[self.p0:p1],
             peak_bytes_in_use=_peak_bytes(jax.devices()[0]),
             **self.fields)
        return True


def _peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_rgb(path: str):
    import numpy as np
    from PIL import Image

    return np.asarray(Image.open(path).convert("RGB"))


def _check_image(img, what: str) -> None:
    size = _config().image_size
    assert img.shape == (size, size, 3), f"{what}: shape {img.shape}"
    # A non-finite latent decodes to a blank (clipped) image.
    assert img.std() > 0, f"{what}: blank image"


def _config():
    from p2p_tpu.models.config import PRESET_CONFIGS

    return PRESET_CONFIGS[PRESET]


def _build_pipe():
    """The pipeline exactly as ``p2p_tpu.cli`` builds it for a preset
    without a checkpoint: random weights from fixed PRNG keys."""
    import jax

    from p2p_tpu.engine.sampler import Pipeline
    from p2p_tpu.models import init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    cfg = _config()
    return Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=HashWordTokenizer(model_max_length=cfg.text.max_length))


# ---------------------------------------------------------------------------
# Phases on one chip
# ---------------------------------------------------------------------------


def phase_edit(clock, work: str) -> None:
    from p2p_tpu import cli

    out_dir = os.path.join(work, "edit")
    with Phase("edit", clock) as ph:
        rc = cli.main(["edit", "--preset", PRESET, "--source", SOURCE,
                       "--target", TARGET, "--steps", str(EDIT_STEPS),
                       "--seeds", ",".join(map(str, EDIT_SEEDS)),
                       "--out-dir", out_dir, "--quiet"])
        assert rc == 0, f"cli edit returned {rc}"
        diffs = []
        for seed in EDIT_SEEDS:
            base = _load_rgb(os.path.join(out_dir, f"{seed:05d}_y.jpg"))
            edit = _load_rgb(os.path.join(out_dir, f"{seed:05d}_y_hat.jpg"))
            _check_image(base, f"seed {seed} baseline")
            _check_image(edit, f"seed {seed} edited")
            diff = float(abs(base.astype(int) - edit.astype(int)).mean())
            assert diff > 0, f"seed {seed}: edited image equals the baseline"
            diffs.append(round(diff, 3))
        ph.note(entry="cli edit", steps=EDIT_STEPS, seeds=list(EDIT_SEEDS),
                dtype="float32", edit_vs_base_mean_abs=diffs)


def expected_kernel_calls(pipe, controller, kernels, itemsize: int):
    """(fused, flash, vae) Pallas calls the ungated edit program must hold
    according to the static plan (``engine.reuse.lower_kernel_plan``): the
    fused-edit sites, the untouched sites that ``nn.fused_attention`` sends
    to the library flash kernel, and the VAE decoder's mid attention."""
    from p2p_tpu.engine import reuse
    from p2p_tpu.kernels import VARIANT_FLASH, VARIANT_FUSED
    from p2p_tpu.models import nn
    from p2p_tpu.models.config import unet_layout

    cfg = pipe.config
    layout = unet_layout(cfg.unet)
    n_cross = sum(1 for m in layout.metas if m.is_cross)
    n_self = len(layout.metas) - n_cross
    ungated = reuse.ReuseSchedule(steps=EDIT_STEPS, cfg_gate=EDIT_STEPS,
                                  cross=(EDIT_STEPS,) * n_cross,
                                  selfa=(EDIT_STEPS,) * n_self)
    (_, variants), = reuse.lower_kernel_plan(layout, ungated, controller,
                                             kernels, phase=1)

    fused = sum(1 for v in variants if v == VARIANT_FUSED)
    flash = sum(1 for m, v in zip(layout.metas, variants)
                if v == VARIANT_FLASH and not m.is_cross
                and nn.takes_flash_kernel(m.pixels, m.channels // m.heads,
                                          itemsize))
    vae_ch = cfg.vae.base_channels * cfg.vae.channel_mults[-1]
    vae = int(nn.takes_flash_kernel(cfg.latent_size ** 2, vae_ch, 4))  # f32
    return fused, flash, vae


def site_parity(ctrl, dtype) -> dict:
    """Each distinct SD-1.4 site geometry the fused kernel covers, run on
    the hardware against the materialized reference on the same random
    q/k/v, inside the cross window and past the self window. Returns
    ``{site: max|Δ| / max|reference|}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.controllers.kernel_spec import kernel_edit_spec
    from p2p_tpu.kernels.fused_edit import (edit_attention_reference,
                                            fused_site_attention)
    from p2p_tpu.models.config import unet_layout

    geometries = {}
    for m in unet_layout(_config().unet).metas:
        if kernel_edit_spec(ctrl, m) is not None:
            geometries.setdefault(
                (m.is_cross, m.pixels, m.channels // m.heads), m)
    worst = {}
    for (cross, pixels, d_head), meta in geometries.items():
        keys = jax.random.split(jax.random.PRNGKey(pixels + d_head), 3)
        q, k, v = (jax.random.normal(key, (4, meta.heads, n, d_head), dtype)
                   for key, n in zip(keys, (pixels, meta.key_len,
                                            meta.key_len)))
        fused = jax.jit(lambda c, q, k, v, s, m=meta, d=d_head:
                        fused_site_attention(q, k, v, d ** -0.5, c, m, s))
        ref = jax.jit(lambda c, q, k, v, s, m=meta, d=d_head:
                      edit_attention_reference(q, k, v, d ** -0.5, c, m, s))
        name = f"{'cross' if cross else 'self'}-P{pixels}-d{d_head}"
        for step in (0, int(0.6 * EDIT_STEPS)):
            got = fused(ctrl, q, k, v, jnp.int32(step))
            assert got is not None, f"the kernel declined site {name}"
            got = np.asarray(got, np.float64)
            want = np.asarray(ref(ctrl, q, k, v, jnp.int32(step)), np.float64)
            assert np.isfinite(got).all(), f"non-finite output at {name}"
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst[name] = max(worst.get(name, 0.0), rel)
    return worst


def phase_kernels(clock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import encode_prompts
    from p2p_tpu.kernels import KernelConfig
    from p2p_tpu.parallel import seed_latents, sweep

    dtype = jnp.bfloat16
    prompts = [SOURCE, TARGET]
    with Phase("kernels", clock) as ph:
        t0 = time.monotonic()
        pipe = _build_pipe()
        jax.block_until_ready((pipe.unet_params, pipe.text_params,
                               pipe.vae_params))
        ph.note(build_seconds=round(time.monotonic() - t0, 3))
        cfg = pipe.config
        # store=False so every edited site is kernel-compilable (a site that
        # stores its maps needs them materialized); self edits up to 32x32.
        ctrl = factory.attention_replace(
            prompts, EDIT_STEPS, cross_replace_steps=0.8,
            self_replace_steps=0.4, tokenizer=pipe.tokenizer,
            self_max_pixels=32 * 32, max_len=cfg.text.max_length,
            store=False)
        kc = KernelConfig()
        fused, flash, vae = expected_kernel_calls(pipe, ctrl, kc, 2)
        assert fused > 0, "the plan fuses no site: the check would be vacuous"

        sites = site_parity(ctrl, dtype)
        ph.note(site_rel_max_abs={k: round(v, 5) for k, v in sites.items()},
                site_rel_limit=SITE_REL_BUDGET)
        bad = {k: v for k, v in sites.items() if v > SITE_REL_BUDGET}
        assert not bad, f"kernel vs reference at single sites: {bad}"

        # One edit group through parallel.sweep — the program the serve
        # engine runs, and the entry point that returns the
        # FINAL latents (text2image hands back the initial x_T, which would
        # compare equal whatever the kernels did).
        def lead(x):
            return jnp.broadcast_to(x[None], (1,) + x.shape)

        cond = encode_prompts(pipe, prompts, dtype=dtype)
        uncond = encode_prompts(pipe, [""] * len(prompts), dtype=dtype)
        ctx = lead(jnp.concatenate([uncond, cond], axis=0))
        lats = seed_latents(jax.random.PRNGKey(EDIT_SEEDS[0]), 1,
                            len(prompts), pipe.latent_shape, dtype=dtype)
        ctrl_g = jax.tree_util.tree_map(lead, ctrl)
        kw = dict(num_steps=EDIT_STEPS, guidance_scale=cfg.guidance_scale)

        text = sweep(pipe, ctx, lats, ctrl_g, kernels=kc, lower_only=True,
                     **kw).compile().as_text()
        calls = text.count('custom_call_target="tpu_custom_call"')
        assert calls == fused + flash + vae, (
            f"compiled program holds {calls} tpu_custom_call(s); the plan "
            f"says {fused} fused + {flash} flash + {vae} vae — a kernel "
            f"gave way to the reference path")

        img_f, lat_f = jax.block_until_ready(
            sweep(pipe, ctx, lats, ctrl_g, kernels=kc, **kw))
        img_m, lat_m = jax.block_until_ready(
            sweep(pipe, ctx, lats, ctrl_g, **kw))
        lat_f = np.asarray(lat_f, np.float64)
        lat_m = np.asarray(lat_m, np.float64)
        img_f, img_m = np.asarray(img_f), np.asarray(img_m)
        assert lat_f.shape == (1, 2) + tuple(pipe.latent_shape), lat_f.shape
        assert np.isfinite(lat_f).all() and np.isfinite(lat_m).all(), \
            "non-finite latents"
        for i, img in enumerate(img_f[0]):
            _check_image(img, f"fused image {i}")
        assert not np.array_equal(lat_m[0, 0], lat_m[0, 1]), \
            "the edit changed nothing"
        mse = float(((lat_f - lat_m) ** 2).mean())
        rel_mse = mse / float((lat_m ** 2).mean())
        max_abs = float(np.abs(lat_f - lat_m).max())
        img_max = int(np.abs(img_f.astype(int) - img_m.astype(int)).max())
        ph.note(entry="parallel.sweep, 1 group", steps=EDIT_STEPS,
                dtype="bfloat16", fused_sites=fused, flash_sites=flash,
                vae_flash_sites=vae, tpu_custom_calls=calls,
                latent_std=float(lat_m.std()), latent_mse=mse,
                latent_rel_mse=rel_mse, latent_rel_mse_limit=LATENT_MSE_BUDGET,
                latent_max_abs=max_abs, image_max_abs=img_max)
        assert rel_mse <= LATENT_MSE_BUDGET, (
            f"fused vs materialized latent MSE {mse} is {rel_mse} of the "
            f"materialized mean square, over the {LATENT_MSE_BUDGET} budget "
            f"(max|Δ|={max_abs})")


def phase_gate(clock, work: str) -> None:
    from p2p_tpu import cli

    left = TIME_LIMIT_S - (time.monotonic() - _T0)
    if left < GATE_NEEDS_S:
        emit(phase="gate", skipped="budget", seconds_left=round(left, 1),
             seconds_needed=GATE_NEEDS_S)
        return
    out_dir = os.path.join(work, "gate")
    seed = EDIT_SEEDS[0]
    with Phase("gate", clock) as ph:
        rc = cli.main(["edit", "--preset", PRESET, "--source", SOURCE,
                       "--target", TARGET, "--steps", str(GATE_STEPS),
                       "--seeds", str(seed), "--gate", "0.5",
                       "--out-dir", out_dir, "--quiet"])
        assert rc == 0, f"cli edit --gate returned {rc}"
        for tag in ("y", "y_hat"):
            _check_image(_load_rgb(os.path.join(out_dir,
                                                f"{seed:05d}_{tag}.jpg")),
                         f"gated {tag}")
        ph.note(entry="cli edit --gate 0.5", steps=GATE_STEPS, seeds=[seed],
                dtype="float32", cut=f"{GATE_STEPS} of 50 steps, 1 seed")


def write_trace(path: str) -> list:
    """The 8-request trace: ``tools/loadgen.py`` draws arrivals, seeds and
    the gate mix (it emits replace edits only); the ungated requests are
    then made refine edits. Two kinds, so three programs instead of five —
    gated replace crosses the phase-1/phase-2 hand-off, ungated refine runs
    the monolithic program — because every SD-1.4 program costs 60-100 s to
    compile. Returns the request ids."""
    loadgen = _load_tool("loadgen")
    rc = loadgen.main(["--n", str(N_REQUESTS), "--mode", "burst",
                       "--burst-size", str(N_REQUESTS), "--seed", "0",
                       "--steps", str(SERVE_STEPS), "--gate-mix", GATE_MIX,
                       "--out", path])
    assert not rc, f"loadgen returned {rc}"
    with open(path) as f:
        reqs = [json.loads(line) for line in f if line.strip()]
    assert len(reqs) == N_REQUESTS, len(reqs)
    for req in reqs:
        if "gate" not in req:
            req["mode"] = "refine"
    assert {(r["mode"], "gate" in r) for r in reqs} == {
        ("replace", True), ("refine", False)}, \
        "the trace does not mix gated replace with ungated refine"
    with open(path, "w") as f:
        for req in reqs:
            f.write(json.dumps(req) + "\n")
    return [r["request_id"] for r in reqs]


def run_serve(trace: str, ids: list, out: str, extra=()) -> dict:
    """``cli serve`` over ``trace``; reads the results file back (the CLI
    returns 0 whatever the terminal records say) and fails on any record
    that is not ``ok``. Returns ``{request_id: [image arrays]}`` plus the
    summary under ``None``."""
    from p2p_tpu import cli

    results = os.path.join(out, "results.jsonl")
    rc = cli.main(["serve", "--preset", PRESET, "--requests", trace,
                   "--results", results,
                   "--journal", os.path.join(out, "wal.jsonl"),
                   "--out-dir", os.path.join(out, "images"), "--quiet",
                   *extra])
    assert rc == 0, f"cli serve returned {rc}"
    with open(results) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    summary = [r for r in recs if r["status"] == "summary"]
    terminal = [r for r in recs if r["status"] != "summary"]
    assert len(summary) == 1, f"{len(summary)} summary records"
    bad = [(r["request_id"], r["status"]) for r in terminal
           if r["status"] != "ok"]
    assert not bad, f"records that are not ok: {bad}"
    assert sorted(r["request_id"] for r in terminal) == sorted(ids), \
        f"terminal records {[r['request_id'] for r in terminal]} != trace"
    images = {None: summary[0]}
    for r in terminal:
        imgs = [_load_rgb(p) for p in r["image_paths"]]
        assert len(imgs) == 2, r["image_paths"]
        for img in imgs:
            _check_image(img, r["request_id"])
        images[r["request_id"]] = imgs
    return images


def phase_serve(clock, work: str) -> None:
    out = os.path.join(work, "serve")
    os.makedirs(out)
    trace = os.path.join(out, "trace.jsonl")
    with Phase("serve", clock) as ph:
        ids = write_trace(trace)
        got = run_serve(trace, ids, out,
                        extra=("--max-batch", str(SERVE_MAX_BATCH)))
        summary = got[None]
        assert summary["counts"]["ok"] == N_REQUESTS, summary["counts"]
        assert summary["phases"]["handoffs"] > 0, \
            "no request crossed the phase-1/phase-2 hand-off"
        ph.note(entry="tools/loadgen.py + cli serve", requests=N_REQUESTS,
                ok=summary["counts"]["ok"], images=2 * N_REQUESTS,
                steps=SERVE_STEPS, dtype="float32",
                cut=f"{SERVE_STEPS} of 50 steps", gate_mix=GATE_MIX,
                max_batch=SERVE_MAX_BATCH,
                kinds="replace gated, refine ungated", handoffs=summary["phases"]["handoffs"],
                n_batches=summary["n_batches"],
                program_cache=summary["program_cache"],
                prewarm_ms=summary["prewarm_ms"],
                makespan_ms=summary["makespan_ms"])


def phase_invert(clock, work: str) -> None:
    import numpy as np
    from PIL import Image

    from p2p_tpu import cli

    left = TIME_LIMIT_S - (time.monotonic() - _T0)
    if left < INVERT_NEEDS_S:
        emit(phase="invert", skipped="budget", seconds_left=round(left, 1),
             seconds_needed=INVERT_NEEDS_S)
        return
    out = os.path.join(work, "invert")
    os.makedirs(out)
    size = _config().image_size
    src = os.path.join(out, "input.png")
    Image.fromarray(np.random.RandomState(0).randint(
        0, 256, (size, size, 3)).astype(np.uint8)).save(src)
    artifact = os.path.join(out, "inversion.npz")
    with Phase("invert", clock) as ph:
        rc = cli.main(["invert", "--preset", PRESET, "--image", src,
                       "--prompt", SOURCE, "--steps", str(INVERT_STEPS),
                       "--inner-steps", str(INVERT_INNER_STEPS),
                       "--artifact", artifact, "--quiet"])
        assert rc == 0, f"cli invert returned {rc}"
        rc = cli.main(["replay", "--preset", PRESET, "--artifact", artifact,
                       "--target", TARGET, "--out-dir", out, "--quiet"])
        assert rc == 0, f"cli replay returned {rc}"
        with np.load(artifact) as art:
            ups = art["uncond_embeddings"]
            assert ups.shape[0] == INVERT_STEPS, ups.shape
            assert np.isfinite(ups).all(), "non-finite null-text embeddings"
        for name in ("reconstruction.png", "edited.png"):
            _check_image(_load_rgb(os.path.join(out, name)), name)
        ph.note(entry="cli invert + cli replay", steps=INVERT_STEPS,
                inner_steps=INVERT_INNER_STEPS, dtype="float32",
                cut=f"{INVERT_STEPS} of 50 steps, {INVERT_INNER_STEPS} of "
                    "10 inner steps")


# ---------------------------------------------------------------------------
# The mesh phase on four chips
# ---------------------------------------------------------------------------


def phase_mesh(clock, work: str, devices) -> None:
    import jax
    import numpy as np

    from p2p_tpu.serve import meshing

    # The engine replicates the weights once per serve run
    # (serve/meshing.py:replicate_pipeline); look at what it placed while
    # the arrays are alive — after cli.main returns they are gone.
    placed = []
    replicate = meshing.replicate_pipeline

    def watching(pipe, mesh):
        rep = replicate(pipe, mesh)
        leaves = jax.tree_util.tree_leaves((rep.unet_params, rep.vae_params))
        sets = {frozenset(x.sharding.device_set) for x in leaves}
        placed.append({
            "mesh_devices": sorted(d.id for d in mesh.devices.flat),
            "weight_device_sets": [sorted(d.id for d in s) for s in sets],
            "weight_bytes_per_device": sum(x.nbytes for x in leaves),
            "live_on": sorted({d.id for x in jax.live_arrays()
                               for d in x.sharding.device_set})})
        return rep

    trace = os.path.join(work, "trace.jsonl")
    runs = {}
    with Phase("mesh", clock) as ph:
        ids = write_trace(trace)
        meshing.replicate_pipeline = watching
        try:
            for dp in (4, 1):
                out = os.path.join(work, f"dp{dp}")
                os.makedirs(out)
                t0 = time.monotonic()
                runs[dp] = run_serve(
                    trace, ids, out,
                    extra=("--mesh", f"dp={dp}",
                           "--max-batch", str(MESH_MAX_BATCH)))
                runs[dp][None]["seconds"] = round(time.monotonic() - t0, 3)
        finally:
            meshing.replicate_pipeline = replicate
        all_ids = sorted(d.id for d in devices)
        dp4, dp1 = placed
        assert dp4["mesh_devices"] == all_ids, dp4
        assert dp4["weight_device_sets"] == [all_ids], \
            f"weights are not on all four devices: {dp4}"
        assert set(all_ids) <= set(dp4["live_on"]), dp4
        assert len(dp1["mesh_devices"]) == 1, dp1
        assert runs[4][None]["mesh"]["dp"] == 4
        assert sorted(runs[4][None]["mesh"]["devices"]) == all_ids
        # Every device did work: its peak allocation is beyond the weights
        # it was handed (activations of its share of the batch).
        peaks = {d.id: _peak_bytes(d) for d in devices}
        idle = [i for i, p in peaks.items()
                if p <= dp4["weight_bytes_per_device"]]
        assert not idle, f"devices {idle} never held more than the weights"
        worst_max, worst_mean, bitwise = 0, 0.0, True
        for rid in ids:
            for a, b in zip(runs[4][rid], runs[1][rid]):
                d = np.abs(a.astype(int) - b.astype(int))
                worst_max = max(worst_max, int(d.max()))
                worst_mean = max(worst_mean, float(d.mean()))
                bitwise = bitwise and not d.any()
        ph.note(entry="cli serve --mesh dp=4 vs --mesh dp=1",
                requests=N_REQUESTS, steps=SERVE_STEPS, dtype="float32",
                max_batch_per_device=MESH_MAX_BATCH,
                mesh_devices=dp4["mesh_devices"],
                weight_bytes_per_device=dp4["weight_bytes_per_device"],
                peak_bytes_by_device=peaks, images_compared=2 * N_REQUESTS,
                bitwise=bitwise, image_max_abs=worst_max,
                image_mean_abs=round(worst_mean, 4),
                image_max_abs_limit=IMAGE_MAX_ABS,
                image_mean_abs_limit=IMAGE_MEAN_ABS,
                dp4_seconds=runs[4][None]["seconds"],
                dp1_seconds=runs[1][None]["seconds"],
                dp4_makespan_ms=runs[4][None]["makespan_ms"],
                dp1_makespan_ms=runs[1][None]["makespan_ms"],
                dp4_handoffs=runs[4][None]["phases"]["handoffs"])
        assert worst_max <= IMAGE_MAX_ABS and worst_mean <= IMAGE_MEAN_ABS, (
            f"dp=4 and dp=1 images differ: max|Δ|={worst_max}, "
            f"mean|Δ|={worst_mean}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 mesh phase and its dp=1 "
                         "comparison (needs four chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: the jax backend is {devices[0].platform!r}, not "
              "tpu — this script has no CPU path", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from p2p_tpu.obs import costmodel
    from p2p_tpu.utils.cache import enable_persistent_cache

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = costmodel.detect_peaks(devices[0])   # raises on an unknown kind
    emit(jax=jax.__version__,
         jaxlib=importlib.metadata.version("jaxlib"),
         libtpu=importlib.metadata.version("libtpu"),
         device_kind=devices[0].device_kind, devices=len(devices),
         peaks_row=peaks.platform,
         compile_cache_dir=enable_persistent_cache(),
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))

    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if args.chips == 4:
            phase_mesh(clock, work, devices)
        else:
            phase_edit(clock, work)
            phase_kernels(clock)
            phase_serve(clock, work)
            phase_gate(clock, work)
            phase_invert(clock, work)
    emit(total_seconds=round(time.monotonic() - _T0, 3),
         compile_seconds=round(clock.seconds, 3),
         programs_compiled=len(clock.programs),
         peak_bytes_by_device={d.id: _peak_bytes(d) for d in devices},
         failed=Phase.failed)
    if Phase.failed:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
