"""Command-line driver.

The reference's CLI (`/root/reference/main.py:386-482`) is a fixed-prompt seed
sweep with a `--type {global,local}` switch and an unread `--path config.yaml`
flag; its real edit surface (`make_controller`) is notebook-only. Here the
whole edit API is on the command line:

    python -m p2p_tpu.cli generate --prompt "a cat" --out out.png
    python -m p2p_tpu.cli edit --source "a cat riding a bike" \
        --target "a dog riding a bike" --mode replace --seeds 1,2,3 \
        --blend-words cat,dog --out-dir logs/run1
    python -m p2p_tpu.cli invert --image cat.png --prompt "a cat" \
        --artifact cat_inv.npz
    python -m p2p_tpu.cli replay --artifact cat_inv.npz \
        --target "a tiger" --mode replace --out-dir logs/replay

Presets: ``tiny``/``tiny_ldm`` (random weights, fast — ``tiny`` is the
default when no checkpoint is given), ``sd14``/``sd21``/``sd21base``/
``ldm256`` (real model shapes; random weights unless ``--checkpoint``
points at a diffusers-format directory; ``sd21`` is the 768-v v-prediction
family the reference marks "Not work"). Every edit run writes the
baseline/edited pair like `run_and_display`
(`/root/reference/main.py:353-383`).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time
from typing import List, Optional

import numpy as np


def _preset_config(name):
    from .models.config import PRESET_CONFIGS

    return PRESET_CONFIGS[name]


def _build_pipeline(args):
    import jax

    from .engine.sampler import Pipeline
    from .models import init_text_encoder
    from .models import vae as vae_mod
    from .models.denoiser import init_denoiser
    from .utils.tokenizer import HashWordTokenizer

    cfg = _preset_config(args.preset)
    if args.checkpoint:
        from .models.checkpoint import load_pipeline

        return load_pipeline(args.checkpoint, cfg)
    tok = HashWordTokenizer(vocab_size=cfg.towers[0].vocab_size,
                            model_max_length=cfg.unet.context_len)
    # The denoiser's and the autoencoder's draws as one program each: the
    # same values, to the bit, as drawing leaf by leaf, without a compile per
    # leaf shape. (The towers' scaled normal tables would round differently.)
    def init(fn, seed, part):
        return jax.jit(fn, static_argnums=1)(jax.random.PRNGKey(seed), part)

    return Pipeline(
        config=cfg,
        unet_params=init(init_denoiser, 0, cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=init(vae_mod.init_vae, 2, cfg.vae),
        tokenizer=tok,
    )


def _save(img: np.ndarray, path: str) -> None:
    from PIL import Image

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    Image.fromarray(np.asarray(img)).save(path)
    print(f"wrote {path}")


@contextlib.contextmanager
def _metrics_session(path: Optional[str]):
    """``--metrics FILE``: run the block under the telemetry collector and
    write a Prometheus text snapshot to FILE afterwards.

    Yields the bool to pass as the engines' ``metrics=`` argument (False
    when no path was given — then nothing extra is traced into any
    program, the disabled-identity contract). On exit the collector drains
    the async callback stream, device ``memory_stats()`` gauges are
    sampled, and the registry (reset at entry, so the snapshot covers
    exactly this run) is rendered to ``path``."""
    if not path:
        yield False
        return
    from .obs import device as obs_device
    from .obs import metrics as obs_metrics

    obs_metrics.registry().reset()
    with obs_device.instrument():
        yield True
    obs_device.sample_device_memory()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(obs_metrics.registry().to_prometheus())
    print(f"wrote {path}", file=sys.stderr)


def _parse_equalizer(spec: Optional[str]):
    if not spec:
        return None
    words, values = [], []
    for part in spec.split(","):
        w, v = part.split("=")
        words.append(w.strip())
        values.append(float(v))
    return {"words": tuple(words), "values": tuple(values)}


def controller_from_opts(prompts, tokenizer, num_steps, *, mode,
                         cross_steps, self_steps, blend_words=None,
                         equalizer=None, blend_resolution=None):
    """The one controller assembly both request surfaces share: the CLI
    subcommands (via ``_make_controller``) and the serving layer
    (``serve.request.prepare``) build edit controllers through this exact
    call, so a spec accepted by one surface is accepted — and means the
    same program — on the other. ``blend_words``/``equalizer`` use the CLI
    string syntax ("cat,dog" / "word=scale,..."). The resolutions nobody
    gave (LocalBlend's maps, the self-injection bound) become the model's
    own levels where the controller meets the pipeline
    (``AttnLayout.resolve``)."""
    from .controllers.factory import make_controller

    blend = blend_words.split(",") if blend_words else None
    if blend is not None:
        blend = [blend] * len(prompts)
    return make_controller(
        prompts,
        is_replace_controller=mode == "replace",
        cross_replace_steps=cross_steps,
        self_replace_steps=self_steps,
        tokenizer=tokenizer,
        num_steps=num_steps,
        blend_words=blend,
        equalizer_params=_parse_equalizer(equalizer),
        blend_resolution=blend_resolution,
    )


def _schedule_spec(args):
    """Load the ``--schedule`` artifact (a reuse-schedule JSON spec) for
    the sampling subcommands; fail fast — before the model build — on a
    bad file or a ``--gate`` conflict (the schedule IS a generalized
    gate)."""
    path = getattr(args, "schedule", None)
    if path is None:
        return None
    if getattr(args, "gate", None) is not None:
        raise SystemExit("--gate and --schedule are mutually exclusive: "
                         "the schedule's cfg_gate is the gate")
    from .engine.reuse import load_spec

    try:
        return load_spec(path)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--schedule {path}: {e}")


def _make_controller(args, prompts, tokenizer, num_steps):
    return controller_from_opts(
        prompts, tokenizer, num_steps, mode=args.mode,
        cross_steps=args.cross_steps, self_steps=args.self_steps,
        blend_words=args.blend_words, equalizer=args.equalizer,
        blend_resolution=args.blend_resolution)


def cmd_generate(args) -> int:
    import jax

    from .engine.sampler import text2image

    from .utils.progress import trace

    sched_spec = _schedule_spec(args)
    pipe = _build_pipeline(args)

    def out_path(seed):
        if len(args.seeds) == 1:
            return args.out
        root, ext = os.path.splitext(args.out)
        return f"{root}_{seed:05d}{ext}"

    if args.batch_seeds:
        from .parallel import sweep

        with _metrics_session(args.metrics) as met, trace(args.profile):
            ctx, lats, mesh = _group_setup(pipe, [args.prompt], args.seeds,
                                           args.negative_prompt)
            imgs, _ = sweep(pipe, ctx, lats, None, num_steps=args.steps,
                            guidance_scale=args.guidance,
                            scheduler=args.scheduler, mesh=mesh,
                            gate=args.gate, schedule=sched_spec,
                            progress=not args.quiet,
                            metrics=met)
            for i, seed in enumerate(args.seeds):
                _save(np.asarray(imgs[i][0]), out_path(seed))
        return 0

    with _metrics_session(args.metrics) as met, trace(args.profile):
        for seed in args.seeds:
            img, _, _ = text2image(pipe, [args.prompt], None,
                                   num_steps=args.steps,
                                   guidance_scale=args.guidance,
                                   scheduler=args.scheduler,
                                   rng=jax.random.PRNGKey(seed),
                                   negative_prompt=args.negative_prompt,
                                   gate=args.gate, schedule=sched_spec,
                                   progress=not args.quiet, metrics=met)
            _save(np.asarray(img[0]), out_path(seed))
    return 0


def _group_setup(pipe, prompts, seeds, negative_prompt):
    """Shared batched-sweep setup: per-group [uncond; cond] context, one
    base latent per seed shared across the group's prompts (the shared-seed
    expansion of `/root/reference/ptp_utils.py:88-95`), and a dp mesh over
    up to min(n_seeds, n_devices) devices (a 4-seed sweep on an 8-device
    slice still rides 4 — same gate as examples/equalizer_sweep.py).
    Returns (ctx (G,2B,L,D), lats (G,B,...), mesh-or-None)."""
    import jax
    import jax.numpy as jnp

    from .engine.sampler import encode_prompts
    from .models.conditioning import cfg_rows
    from .parallel import make_mesh

    g = len(seeds)
    cond = encode_prompts(pipe, prompts)
    uncond = encode_prompts(pipe, [negative_prompt or ""] * len(prompts))
    ctx = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (g,) + a.shape),
                       cfg_rows(uncond, cond))
    base = jnp.stack([jax.random.normal(jax.random.PRNGKey(s),
                                        (1,) + pipe.latent_shape)
                      for s in seeds])
    lats = jnp.broadcast_to(base, (g, len(prompts)) + pipe.latent_shape)
    return ctx, lats, _dp_mesh(g, f"--batch-seeds: {g} seeds")


def _dp_mesh(g, what):
    """Shard over the largest divisor of g that fits the visible devices
    (g=6 on 4 devices rides 3, not 1); say so when parallelism degrades,
    rather than silently losing what the batch flag advertises."""
    import jax

    from .parallel import make_mesh

    cap = min(len(jax.devices()), g)
    n_dev = max((d for d in range(1, cap + 1) if g % d == 0), default=1)
    if n_dev < cap:
        print(f"{what} not divisible by {cap} devices; "
              f"sharding over {n_dev}", file=sys.stderr)
    return make_mesh(n_dev) if n_dev > 1 else None


def _edit_batched(args, pipe, prompts, controller, out_dir,
                  metrics: bool = False) -> int:
    """The seed sweep as two compiled programs total (baseline + edit), all
    seeds riding the group axis of the dp sweep engine — the reference's
    sequential per-seed loop (`/root/reference/main.py:417-444`) at sweep
    throughput."""
    import jax
    import jax.numpy as jnp

    from .parallel import sweep

    g = len(args.seeds)
    ctx, lats, mesh = _group_setup(pipe, prompts, args.seeds,
                                   args.negative_prompt)
    kw = dict(num_steps=args.steps, guidance_scale=args.guidance,
              scheduler=args.scheduler, mesh=mesh, gate=args.gate,
              schedule=_schedule_spec(args),
              progress=not args.quiet, metrics=metrics)
    base_imgs, _ = sweep(pipe, ctx, lats, None, **kw)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (g,) + x.shape), controller)
    edit_imgs, _ = sweep(pipe, ctx, lats, ctrls, **kw)
    for i, seed in enumerate(args.seeds):
        _save(np.asarray(base_imgs[i][0]),
              os.path.join(out_dir, f"{seed:05d}_y.jpg"))
        _save(np.asarray(edit_imgs[i][1]),
              os.path.join(out_dir, f"{seed:05d}_y_hat.jpg"))
    return 0


def cmd_edit(args) -> int:
    import jax

    from .engine.sampler import text2image

    from .utils.progress import trace

    if args.batch_seeds and (args.attn_maps or args.self_attn_maps):
        # Batched groups carry a leading G axis in the store state the viz
        # aggregation doesn't index; honored-flags discipline says reject
        # rather than silently ignore — and before the model load.
        raise SystemExit("--attn-maps/--self-attn-maps require the "
                         "sequential path (drop --batch-seeds)")
    pipe = _build_pipeline(args)
    prompts = [args.source, args.target]
    controller = _make_controller(args, prompts, pipe.tokenizer, args.steps)
    out_dir = args.out_dir or os.path.join("logs", time.strftime("%y%m%d_%H%M%S"))
    if args.batch_seeds:
        with _metrics_session(args.metrics) as met, trace(args.profile):
            return _edit_batched(args, pipe, prompts, controller, out_dir,
                                 metrics=met)
    from .models.config import denoiser_layout

    layout = denoiser_layout(pipe.config.unet)
    sched_spec = _schedule_spec(args)
    with _metrics_session(args.metrics) as met, trace(args.profile):
        for seed in args.seeds:
            rng = jax.random.PRNGKey(seed)
            base, x_t, _ = text2image(pipe, prompts, None,
                                      num_steps=args.steps,
                                      guidance_scale=args.guidance,
                                      scheduler=args.scheduler, rng=rng,
                                      negative_prompt=args.negative_prompt,
                                      gate=args.gate, schedule=sched_spec,
                                      progress=not args.quiet, layout=layout,
                                      metrics=met)
            img, _, store = text2image(pipe, prompts, controller,
                                       num_steps=args.steps,
                                       guidance_scale=args.guidance,
                                       scheduler=args.scheduler, latent=x_t,
                                       negative_prompt=args.negative_prompt,
                                       gate=args.gate, schedule=sched_spec,
                                       progress=not args.quiet, layout=layout,
                                       metrics=met,
                                       return_store=bool(args.attn_maps
                                                         or args.self_attn_maps))
            # y / y_hat naming per `/root/reference/main.py:375-380,435-444`.
            _save(np.asarray(base[0]),
                  os.path.join(out_dir, f"{seed:05d}_y.jpg"))
            _save(np.asarray(img[1]),
                  os.path.join(out_dir, f"{seed:05d}_y_hat.jpg"))
            if args.attn_maps:
                _save_attn_maps(args, pipe, layout, store, seed)
            if args.self_attn_maps:
                _save_self_attn_maps(args, pipe, layout, store, seed)
    return 0


def _save_attn_maps(args, pipe, layout, store, seed) -> None:
    """Per-token cross-attention heatmaps of the edited prompt — the
    reference's `show_cross_attention` notebook workflow
    (`/root/reference/main.py:310-327`) as a CLI artifact."""
    from .utils import viz

    res = _stored_res(layout, pipe, cross=True, flag="--attn-maps")
    os.makedirs(args.attn_maps, exist_ok=True)
    viz.show_cross_attention(
        pipe.tokenizer, args.target, layout, store, args.steps, res,
        ("up", "down"), select=1,
        save_path=os.path.join(args.attn_maps, f"{seed:05d}_cross_attn.png"))


def _stored_res(layout, pipe, cross: bool, flag: str) -> int:
    """Model-derived display resolution: the largest stored resolution ≤ a
    quarter of the latent side (the 16×16 level the reference reads at SD's
    64² latent, `/root/reference/main.py:302,327`), falling back to the
    largest stored at all (tiny test models)."""
    stored = sorted({m.resolution for m in layout.stored_metas()
                     if m.is_cross == cross and m.place in ("up", "down")})
    if not stored:
        kind = "cross" if cross else "self"
        raise SystemExit(f"{flag}: no stored up/down {kind}-attention "
                         "sites in this model config")
    want = pipe.config.unet.sample_size // 4
    return max((r for r in stored if r <= want), default=stored[-1])


def _save_self_attn_maps(args, pipe, layout, store, seed) -> None:
    """Top-10 SVD components of the self-attention matrix — the reference's
    `show_self_attention_comp` notebook workflow
    (`/root/reference/main.py:330-350`) as a CLI artifact."""
    from .utils import viz

    res = _stored_res(layout, pipe, cross=False, flag="--self-attn-maps")
    os.makedirs(args.self_attn_maps, exist_ok=True)
    viz.show_self_attention_comp(
        layout, store, args.steps, res, ("up", "down"), select=1,
        save_path=os.path.join(args.self_attn_maps,
                               f"{seed:05d}_self_attn_svd.png"))


def cmd_invert(args) -> int:
    from .engine.inversion import invert, load_image

    from .utils.progress import trace

    pipe = _build_pipeline(args)
    image = load_image(args.image, size=pipe.config.image_size)
    with _metrics_session(args.metrics) as met, trace(args.profile):
        art = invert(pipe, image, args.prompt, num_steps=args.steps,
                     guidance_scale=args.guidance,
                     num_inner_steps=args.inner_steps,
                     progress=not args.quiet, metrics=met)
    art.save(args.artifact)
    print(f"wrote {args.artifact}")
    if args.out_dir:
        _save(art.image_gt, os.path.join(args.out_dir, "gt.png"))
        _save(art.image_rec, os.path.join(args.out_dir, "vae_rec.png"))
    return 0


def cmd_replay(args) -> int:
    import jax.numpy as jnp

    from .engine.inversion import InversionArtifact
    from .engine.sampler import text2image
    from .utils.progress import trace

    targets = args.target or []
    if args.batch_targets and not targets:
        raise SystemExit("--batch-targets needs at least one --target")
    pipe = _build_pipeline(args)
    art = InversionArtifact.load(args.artifact)
    out_dir = args.out_dir or "outputs"

    def edited_path(i):
        return os.path.join(
            out_dir, "edited.png" if len(targets) == 1
            else f"edited_{i:02d}.png")

    if args.batch_targets:
        return _replay_batched(args, pipe, art, targets, out_dir, edited_path)

    x_t = jnp.asarray(art.x_t)
    ups = jnp.asarray(art.uncond_embeddings)
    with _metrics_session(args.metrics) as met, trace(args.profile):
        for i, target in enumerate(targets or [None]):
            prompts = [art.prompt, target] if target else [art.prompt]
            controller = (None if target is None else _make_controller(
                args, prompts, pipe.tokenizer, art.num_steps))
            img, _, _ = text2image(
                pipe, prompts, controller, num_steps=art.num_steps,
                guidance_scale=args.guidance, latent=x_t,
                uncond_embeddings=ups, progress=not args.quiet, metrics=met)
            if i == 0:
                _save(np.asarray(img[0]),
                      os.path.join(out_dir, "reconstruction.png"))
            if target is not None:
                _save(np.asarray(img[1]), edited_path(i))
    return 0


def _replay_batched(args, pipe, art, targets, out_dir, edited_path) -> int:
    """All target edits of one inversion artifact as ONE compiled dp-swept
    program: each group is [source, target_i] with the artifact's per-step
    null embeddings broadcast over groups — the missing-notebook workflow
    (`/root/reference/null_text.py:618` + SURVEY §3.2) at sweep throughput.
    Target controllers are traced leaves of one stacked pytree, so they must
    share structure: one --mode/--blend-words/--equalizer for all targets."""
    from .parallel import artifact_replay_inputs, sweep
    from .utils.progress import trace

    g = len(targets)
    ctrl_list = [_make_controller(args, [art.prompt, t], pipe.tokenizer,
                                  art.num_steps) for t in targets]
    ctx_g, lats, ups, ctrls = artifact_replay_inputs(
        pipe, art.x_t, art.uncond_embeddings, art.prompt, targets, ctrl_list)
    with _metrics_session(args.metrics) as met, trace(args.profile):
        imgs, _ = sweep(pipe, ctx_g, lats, ctrls, num_steps=art.num_steps,
                        guidance_scale=args.guidance,
                        mesh=_dp_mesh(g, f"--batch-targets: {g} targets"),
                        uncond_per_step=ups, progress=not args.quiet,
                        metrics=met)
        imgs = np.asarray(imgs)
    _save(imgs[0][0], os.path.join(out_dir, "reconstruction.png"))
    for i in range(g):
        _save(imgs[i][1], edited_path(i))
    return 0


def cmd_serve(args) -> int:
    """Request-level serving: drain a JSONL request trace through the
    serve subsystem (queue → dynamic batcher → program cache → worker
    loop), writing one JSONL record per request plus a summary. See
    docs/SERVING.md for the request schema."""
    import json

    from .obs import metrics as obs_metrics
    from .obs import spans as obs_spans
    from .serve import (DegradeConfig, DrainController, FaultPlan, Journal,
                        Request, parse_jsonl_line, parse_mesh,
                        serve_forever, signal_drain)

    if args.snapshot_every_ms is not None and not args.journal:
        # Fail fast, before the (expensive) pipeline build.
        raise SystemExit("--snapshot-every-ms snapshots the journal: it "
                         "needs --journal")
    mesh_spec = None
    if args.mesh:
        try:
            # Parse before the pipeline build (fail fast on a typo); the
            # device-count check happens when the engine builds the live
            # mesh, after backend init.
            mesh_spec = parse_mesh(args.mesh)
        except ValueError as e:
            raise SystemExit(str(e))
    elastic_cfg = None
    if args.elastic is not None:
        from .serve import parse_elastic

        try:
            elastic_cfg = parse_elastic(args.elastic)
        except ValueError as e:
            raise SystemExit(str(e))
    # One serve run == one snapshot/event-log: reset before the pipeline
    # build so prewarm compiles and the queue/batcher/cache timelines are
    # all covered by the exported artifacts.
    obs_metrics.registry().reset()
    obs_spans.clear()
    ring = args.events_ring
    if ring is None:
        env_ring = os.environ.get("P2P_OBS_EVENTS_RING")
        if env_ring:
            try:
                ring = int(env_ring)
            except ValueError:
                raise SystemExit(f"P2P_OBS_EVENTS_RING must be an integer, "
                                 f"got {env_ring!r}")
    if ring is not None:
        if ring < 1:
            raise SystemExit(f"--events-ring must be >= 1, got {ring}")
        obs_spans.set_capacity(ring)
    flight_tracer = None
    if args.flight_out or args.trace_out or args.blackbox:
        from .obs import flight as obs_flight

        flight_tracer = obs_flight.FlightTracer(blackbox_dir=args.blackbox)
    costscope = None
    if args.cost or args.programs_out:
        from .obs import costmodel as obs_costmodel

        costscope = obs_costmodel.CostScope()
    default_sched = _schedule_spec(args)
    prodscope = None
    if args.profile:
        from .obs import prodscope as obs_prodscope

        tags = {"preset": args.preset, "max_batch": args.max_batch}
        if args.mesh:
            tags["mesh"] = args.mesh
        if args.phase2_max_batch is not None:
            tags["phase2_max_batch"] = args.phase2_max_batch
        if default_sched is not None:
            tags["schedule"] = default_sched
        try:
            prodscope = obs_prodscope.ProdScope(
                args.profile, seed=args.profile_seed,
                period=args.profile_every,
                ring_max_bytes=args.profile_ring_bytes,
                ring_max_count=args.profile_ring_count, tags=tags)
        except ValueError as e:
            raise SystemExit(f"--profile: {e}")
    elif (args.profile_every != 8 or args.profile_seed != 0
          or args.profile_ring_bytes != 256 << 20
          or args.profile_ring_count != 16):
        raise SystemExit("--profile-every/--profile-seed/--profile-ring-"
                         "bytes/--profile-ring-count configure the "
                         "production profiler: they need --profile DIR")
    pipe = _build_pipeline(args)
    stream = sys.stdin if args.requests == "-" else open(args.requests)
    items = []
    with stream:
        for i, line in enumerate(stream):
            try:
                item = parse_jsonl_line(line)
            except (ValueError, KeyError) as e:
                raise SystemExit(f"--requests line {i + 1}: {e}")
            if item is None:
                continue
            if default_sched is not None and isinstance(item, Request) \
                    and item.gate is None and item.schedule is None:
                # The server default applies only where the request left
                # BOTH knobs unset: an explicit per-request gate or
                # schedule always wins (and gate+schedule stays a clean
                # per-request schema reject).
                import dataclasses as _dc

                item = _dc.replace(item, schedule=default_sched)
            items.append(item)
    prewarm = None
    if not args.no_prewarm:
        # Compile-ahead with the first request as the representative shape:
        # uniform traffic then never pays a compile in-band.
        prewarm = [r for r in items if isinstance(r, Request)][:1]

    journal = Journal(args.journal) if args.journal else None
    chaos = FaultPlan.load(args.chaos_plan) if args.chaos_plan else None
    if chaos is not None:
        # Some kinds are inert without their enabling flag: a drill that
        # "passes" without ever exercising the path is worse than one that
        # fails, so say so up front. The per-kind conditions and texts
        # live in the chaos-kind catalog (serve/chaos.CATALOG) next to
        # each kind's crash-window declaration.
        from .serve.chaos import inert_warnings

        kinds = set(chaos.by_batch.values()) | set(chaos.by_request.values())
        for msg in inert_warnings(kinds, {
                "validate_outputs": args.validate_outputs,
                "watchdog_ms": args.watchdog_ms,
                "journal": args.journal,
                "snapshot_every_ms": args.snapshot_every_ms,
                "cache": args.cache,
                "profile": args.profile,
                "elastic": args.elastic}):
            print(f"warning: {msg}", file=sys.stderr)
    degrade = None
    if args.degrade_depth is not None:
        degrade = DegradeConfig(depth_threshold=args.degrade_depth,
                                window_ms=args.degrade_window_ms,
                                min_bucket=args.degrade_min_bucket)
    semcache = None
    if args.cache:
        from .serve import SemCache

        try:
            semcache = SemCache(
                spill_dir=args.cache_dir,
                **({"l3_bytes": args.cache_l3_bytes}
                   if args.cache_l3_bytes is not None else {}))
        except ValueError as e:
            raise SystemExit(str(e))
    elif args.cache_dir is not None or args.cache_l3_bytes is not None:
        raise SystemExit("--cache-dir/--cache-l3-bytes configure the "
                         "semantic cache: they need --cache")
    slo = None
    if args.slo or args.tenant_quota is not None \
            or args.preempt_depth is not None:
        from .serve import SloConfig

        try:
            slo = SloConfig(tenant_quota=args.tenant_quota,
                            preempt_depth=args.preempt_depth)
        except ValueError as e:
            raise SystemExit(str(e))
        if args.preempt_depth is not None and not args.journal:
            print("warning: --preempt-depth without --journal parks "
                  "preempted carries in memory only — a crash mid-park "
                  "re-runs phase 1 instead of resuming off a spill",
                  file=sys.stderr)

    out = open(args.results, "w") if args.results else sys.stdout

    def emit(rec):
        rec = dict(rec)
        images = rec.pop("images", None)
        if images is not None and args.out_dir:
            names = ([f"{rec['request_id']}.png"] if len(images) == 1 else
                     [f"{rec['request_id']}_y.png",
                      f"{rec['request_id']}_y_hat.png"])
            rec["image_paths"] = [os.path.join(args.out_dir, n)
                                  for n in names]
            from PIL import Image

            os.makedirs(args.out_dir, exist_ok=True)
            for img, path in zip(images, rec["image_paths"]):
                # Not _save: its "wrote ..." print would interleave with
                # JSONL records when results go to stdout.
                Image.fromarray(np.asarray(img)).save(path)
        out.write(json.dumps(rec) + "\n")
        out.flush()

    # Lifecycle: SIGTERM/Ctrl-C request a graceful drain (finish in-flight
    # work, snapshot, emit the summary, exit 0); a second signal forces a
    # KeyboardInterrupt, caught below so the operator never sees a raw
    # traceback — artifacts still flush in the finally blocks and the
    # journal's crash contract covers whatever the force-quit abandoned.
    drain_ctl = DrainController()
    interrupted = False
    try:
        with signal_drain(drain_ctl):
            for rec in serve_forever(
                    pipe, items, max_batch=args.max_batch,
                    max_wait_ms=args.max_wait_ms, queue_cap=args.queue_cap,
                    program_cache_cap=args.program_cache_cap,
                    prewarm=prewarm, progress=not args.quiet,
                    journal=journal, chaos=chaos,
                    watchdog_ms=args.watchdog_ms,
                    validate_outputs=args.validate_outputs,
                    degrade=degrade,
                    phase_pools=not args.single_pool,
                    phase2_max_batch=args.phase2_max_batch,
                    mesh=mesh_spec,
                    elastic=elastic_cfg,
                    slo=slo,
                    semcache=semcache,
                    costscope=costscope,
                    prodscope=prodscope,
                    flight=flight_tracer,
                    lifecycle=drain_ctl,
                    snapshot_every_ms=args.snapshot_every_ms,
                    drain_timeout_ms=args.drain_timeout_ms):
                emit(rec)
    except KeyboardInterrupt:
        interrupted = True
        print("serve: force quit before the drain completed (journaled "
              "work resumes on restart)", file=sys.stderr)
    finally:
        if journal is not None:
            journal.close()
        if out is not sys.stdout:
            out.close()
        if prodscope is not None:
            # Written in the finally so a fatal drain's captures still
            # persist their ledger.
            try:
                path = prodscope.write_ledger()
            except OSError as e:
                print(f"--profile: ledger write failed: {e}",
                      file=sys.stderr)
            else:
                print(f"wrote {path}", file=sys.stderr)
        if costscope is not None and args.programs_out:
            # Written in the finally so a fatal drain's cards (and a
            # partially-drained trace) still produce the artifact.
            os.makedirs(os.path.dirname(args.programs_out) or ".",
                        exist_ok=True)
            with open(args.programs_out, "w") as f:
                costscope.write_programs_jsonl(f)
            print(f"wrote {args.programs_out}", file=sys.stderr)
        if flight_tracer is not None:
            # Written in the finally so a fatal drain's records (and a
            # partially-drained trace) still produce the artifacts.
            from .obs import flight as obs_flight

            if args.flight_out:
                os.makedirs(os.path.dirname(args.flight_out) or ".",
                            exist_ok=True)
                with open(args.flight_out, "w") as f:
                    obs_flight.write_flight_jsonl(f, flight_tracer.records)
                print(f"wrote {args.flight_out}", file=sys.stderr)
            if args.trace_out:
                os.makedirs(os.path.dirname(args.trace_out) or ".",
                            exist_ok=True)
                with open(args.trace_out, "w") as f:
                    json.dump(obs_flight.chrome_trace(flight_tracer), f)
                    f.write("\n")
                print(f"wrote {args.trace_out}", file=sys.stderr)
    if args.metrics_out or args.events_out:
        from .obs import device as obs_device

        obs_device.sample_device_memory()
        for path, render in ((args.metrics_out,
                              obs_metrics.registry().to_prometheus),
                             (args.events_out, None)):
            if not path:
                continue
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                if render is not None:
                    f.write(render())
                else:
                    obs_spans.write_jsonl(f)
            print(f"wrote {path}", file=sys.stderr)
    return 130 if interrupted else 0


def cmd_check(args) -> int:
    if args.ast_only and not args.static:
        # Honored-flags discipline: never accept-and-ignore.
        raise SystemExit("--ast-only only applies to --static")
    if args.static:
        if args.checkpoint_dir or args.preset:
            raise SystemExit("--static is the whole-stack analyzer; it "
                             "takes no checkpoint_dir/--preset")
        if not args.ast_only:
            # Same backend pinning as tools/jaxcheck.py (one shared
            # helper): the traced passes are structure checks, never
            # device work — tracing on an accelerator would initialize it
            # (and could lower donation differently), and a one-device
            # run would degrade the shardcheck sweep to dp=1, where a
            # real hidden all-gather at dp>=2 passes unseen.
            from .utils.platform import force_cpu_platform

            force_cpu_platform()
            import jax

            jax.config.update("jax_platforms", "cpu")
        from .analysis import report as report_mod

        report = report_mod.run_all(ast_only=args.ast_only)
        print(report_mod.render_text(report))
        return 0 if report["ok"] else 1
    if not args.checkpoint_dir or not args.preset:
        raise SystemExit("check needs a checkpoint_dir and --preset "
                         "(or --static for the static analyzer)")
    from .models.checkpoint_check import _print_report, check_checkpoint

    rep = check_checkpoint(args.checkpoint_dir, args.preset)
    _print_report(rep)
    return 0 if rep.ok else 1


def _int_list(s: str) -> List[int]:
    return [int(x) for x in s.split(",") if x]


def _gate_spec(s: str):
    """Parse ``--gate``: 'auto' | a fraction with a dot ('0.5') | an absolute
    step index ('25'). Kept jax-free; full validation (range, controller
    window, null-text conflicts) happens in ``engine.sampler.resolve_gate``."""
    if s == "auto":
        return "auto"
    try:
        return float(s) if "." in s else int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--gate expects 'auto', a fraction like 0.5, or a step index, "
            f"got {s!r}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="p2p_tpu", description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    # Each subcommand declares exactly the flags it honors — no
    # accepted-but-ignored options (the reference's unread `--path
    # config.yaml`, `/root/reference/main.py:388`, is the anti-pattern).
    def model_opts(sp, guidance=True, metrics=True, profile=True):
        # Literal name tuples: build_parser must stay jax-free so --help and
        # argparse errors are instant. Drift against the canonical
        # PRESET_CONFIGS map is pinned by
        # tests/test_cli.py::test_every_cli_preset_resolves_to_a_config.
        sp.add_argument("--preset",
                        choices=("tiny", "sd14", "sd21", "sd21base",
                                 "ldm256", "tiny_ldm", "tiny_v", "sdxl",
                                 "tiny_xl", "pixart_sigma", "tiny_dit"),
                        default="tiny",
                        help="model family; sd21 is the 768-v v-prediction "
                             "variant the reference marks 'Not work' "
                             "(`/root/reference/main.py:27`) — supported "
                             "here")
        sp.add_argument("--checkpoint", default=None,
                        help="diffusers-format checkpoint dir (unet/ vae/ ...)")
        if guidance:
            # serve omits this: guidance is a per-request JSONL field there
            # (honored-flags discipline — no accepted-but-ignored options).
            sp.add_argument("--guidance", type=float, default=7.5)
        sp.add_argument("--quiet", action="store_true",
                        help="suppress per-step progress output")
        if profile:
            # serve defines its own --profile (the production profiler's
            # ring + ledger directory, ISSUE 18) — a whole-run
            # jax.profiler trace of a server is the wrong tool there.
            sp.add_argument("--profile", default=None, metavar="DIR",
                            help="write a jax.profiler trace of the run "
                                 "to DIR")
        if metrics:
            # serve surfaces its own --metrics-out/--events-out pair (the
            # registry there also carries queue/batcher/cache families).
            sp.add_argument("--metrics", default=None, metavar="FILE",
                            help="enable device-side telemetry (per-phase "
                                 "step timing via the host-callback "
                                 "channel, memory gauges) and write a "
                                 "Prometheus text snapshot of the run to "
                                 "FILE (docs/OBSERVABILITY.md)")

    def sampling_opts(sp):
        sp.add_argument("--steps", type=int, default=50)
        sp.add_argument("--scheduler", choices=("ddim", "plms", "dpm"), default="ddim")
        sp.add_argument("--seeds", type=_int_list, default=[8191],
                        help="comma-separated seed sweep")
        sp.add_argument("--gate", type=_gate_spec, default=None,
                        metavar="AUTO|FRAC|STEP",
                        help="phase-gated sampling: steps past the gate run "
                             "a single-branch U-Net (CFG folded into a "
                             "fixed extrapolation) with cached "
                             "cross-attention — 'auto' picks max(T/2, the "
                             "controller's edit-window end); 0.5 gates at "
                             "half the steps; an integer is an absolute "
                             "step. Omit for exact (ungated) sampling")
        sp.add_argument("--schedule", default=None, metavar="FILE",
                        help="per-site per-step reuse schedule artifact "
                             "(JSON, e.g. tools/schedules/default_v1.json):"
                             " the generalized gate — each attention site "
                             "flips to cached/inherited reuse at its own "
                             "step. Mutually exclusive with --gate")

    def edit_opts(sp):
        sp.add_argument("--mode", choices=("replace", "refine"),
                        default="refine")
        sp.add_argument("--cross-steps", type=float, default=0.8)
        sp.add_argument("--self-steps", type=float, default=0.4)
        sp.add_argument("--blend-words", default=None,
                        help="comma-separated words for LocalBlend masking")
        sp.add_argument("--equalizer", default=None,
                        help="word=scale[,word=scale...] reweighting")
        sp.add_argument("--blend-resolution", type=int, default=None,
                        help="side of the cross-attention maps LocalBlend "
                             "masks with (default: the model's level in the "
                             "place of SD-1.4's 16: 24 for sd21)")

    def negative_opt(sp):
        # generate/edit only — replay's uncond comes from the inversion
        # artifact, invert's from the null-text objective (honored-flags-only
        # discipline: no accepted-but-ignored options).
        sp.add_argument("--negative-prompt", default=None,
                        help='steer CFG away from this text instead of ""')

    g = sub.add_parser("generate", help="text-to-image, no editing")
    model_opts(g); sampling_opts(g); negative_opt(g)
    g.add_argument("--prompt", required=True)
    g.add_argument("--out", default="outputs/image.png",
                   help="output path; seed index suffixed when sweeping")
    g.add_argument("--batch-seeds", action="store_true",
                   help="run the whole seed sweep as one batched program "
                        "through the dp sweep engine")
    g.set_defaults(fn=cmd_generate)

    e = sub.add_parser("edit", help="prompt-to-prompt edit with seed sweep")
    model_opts(e); sampling_opts(e); edit_opts(e); negative_opt(e)
    e.add_argument("--source", required=True, help="source prompt")
    e.add_argument("--target", required=True, help="edited prompt")
    e.add_argument("--out-dir", default=None)
    e.add_argument("--batch-seeds", action="store_true",
                   help="run the whole seed sweep as batched edit groups "
                        "through the dp sweep engine (two compiled programs "
                        "total instead of two per seed; sharded over the "
                        "mesh when more than one device is visible)")
    e.add_argument("--attn-maps", default=None, metavar="DIR",
                   help="also write per-token cross-attention heatmaps of "
                        "the edited prompt (the reference's "
                        "show_cross_attention) into DIR")
    e.add_argument("--self-attn-maps", default=None, metavar="DIR",
                   help="also write the top-10 self-attention SVD "
                        "components of the edited image (the reference's "
                        "show_self_attention_comp) into DIR")
    e.set_defaults(fn=cmd_edit)

    # Inversion is DDIM by construction (`/root/reference/null_text.py:23`);
    # no --scheduler/--seeds here.
    i = sub.add_parser("invert", help="null-text inversion of a real image")
    model_opts(i)
    i.add_argument("--steps", type=int, default=50)
    i.add_argument("--image", required=True)
    i.add_argument("--prompt", required=True)
    i.add_argument("--artifact", default="outputs/inversion.npz")
    i.add_argument("--inner-steps", type=int, default=10)
    i.add_argument("--out-dir", default=None,
                   help="also write gt.png / vae_rec.png here")
    i.set_defaults(fn=cmd_invert)

    # Replay inherits step count and scheduler from the artifact.
    r = sub.add_parser("replay", help="edit a previously inverted image")
    model_opts(r); edit_opts(r)
    r.add_argument("--artifact", required=True)
    r.add_argument("--target", action="append", default=None,
                   help="edited prompt; repeatable for a target sweep "
                        "(omit for pure reconstruction)")
    r.add_argument("--out-dir", default=None)
    r.add_argument("--batch-targets", action="store_true",
                   help="run all --target edits of the artifact as one "
                        "batched program through the dp sweep engine "
                        "(one edit group per target, sharded over the mesh; "
                        "all targets share --mode/--blend-words/--equalizer)")
    r.set_defaults(fn=cmd_replay)

    s = sub.add_parser(
        "serve",
        help="request-level serving: JSONL requests in, JSONL records out")
    model_opts(s, guidance=False, metrics=False, profile=False)
    s.add_argument("--requests", required=True,
                   help="JSONL request trace: a file, a FIFO, or '-' for "
                        "stdin (schema: docs/SERVING.md; generator: "
                        "tools/loadgen.py)")
    s.add_argument("--results", default=None, metavar="FILE",
                   help="write per-request result records here "
                        "(default: stdout)")
    s.add_argument("--out-dir", default=None, metavar="DIR",
                   help="also write served images here "
                        "(<id>.png, or <id>_y.png/<id>_y_hat.png for edits)")
    s.add_argument("--max-batch", type=int, default=8, choices=(1, 2, 4, 8),
                   help="flush a compile-key bucket at this many requests "
                        "(must be one of the fixed padding buckets)")
    s.add_argument("--max-wait-ms", type=float, default=50.0,
                   help="flush a partial bucket after its oldest request "
                        "has waited this long")
    s.add_argument("--phase2-max-batch", type=int, default=None,
                   choices=(1, 2, 4, 8), metavar="N",
                   help="lane-bucket cap of the phase-2 pool (gated "
                        "requests past the hand-off; default: one fixed "
                        "bucket above --max-batch — phase-2 lanes carry no "
                        "CFG uncond half, so 2x the lanes fit the same "
                        "peak footprint)")
    s.add_argument("--mesh", default=None, metavar="dp=N",
                   help="mesh-parallel serving: shard every dispatched "
                        "batch over an N-device data-parallel mesh (lane "
                        "buckets become per-device sub-batches; --max-batch "
                        "and --phase2-max-batch keep their per-device "
                        "meaning, so the global bucket set scales to "
                        "N x {1,2,4,8}). N must be a power of two and at "
                        "most the process's device count. dp=1 is bitwise-"
                        "identical to serving without the flag; journal/"
                        "drain/crash semantics are mesh-agnostic "
                        "(docs/SERVING.md#mesh-parallel-serving)")
    s.add_argument("--elastic", default=None, nargs="?", const="on",
                   metavar="on|k=v,...",
                   help="elastic mesh serving: a pressure-driven controller "
                        "resizes the data-parallel mesh between powers of "
                        "two while serving (prewarm-before-cutover, "
                        "journaled resize protocol, in-flight work parks "
                        "and resumes exactly-once). 'on' takes the "
                        "defaults; otherwise a comma list over up_depth/"
                        "up_window_ms/down_depth/down_window_ms/"
                        "cooldown_ms/min_dp/max_dp. Combines with --mesh "
                        "as the starting topology (default dp=1) — "
                        "docs/SERVING.md#elastic-meshes")
    s.add_argument("--single-pool", action="store_true",
                   help="disable phase-disaggregated continuous batching: "
                        "gated requests run their monolithic program in "
                        "one pool (the pre-disaggregation engine)")
    s.add_argument("--schedule", default=None, metavar="FILE",
                   help="default per-site reuse schedule artifact (JSON, "
                        "e.g. tools/schedules/default_v1.json) applied to "
                        "every request that sets neither 'gate' nor its "
                        "own 'schedule' field; per-request schedules "
                        "override (docs/SERVING.md)")
    s.add_argument("--queue-cap", type=int, default=64,
                   help="admission bound on outstanding requests; beyond "
                        "it, requests are rejected with a reason "
                        "(backpressure, never a silent drop)")
    s.add_argument("--program-cache-cap", type=int, default=8,
                   help="LRU capacity of the compiled-program cache")
    s.add_argument("--no-prewarm", action="store_true",
                   help="skip compile-ahead of the first request's program "
                        "(compiles then happen in-band on first dispatch)")
    s.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write a Prometheus text snapshot of the serve "
                        "telemetry registry (queue depth, stage-latency "
                        "histograms, program-cache counters, memory "
                        "gauges) here after the trace drains "
                        "(docs/OBSERVABILITY.md)")
    s.add_argument("--events-out", default=None, metavar="FILE",
                   help="write the structured span event log "
                        "(serve.prewarm / serve.batch / serve.isolate_retry "
                        "start/stop events, JSONL) here after the trace "
                        "drains")
    s.add_argument("--events-ring", type=int, default=None, metavar="N",
                   help="span ring-buffer capacity (default 4096, or the "
                        "P2P_OBS_EVENTS_RING env var): two-pool serving "
                        "roughly doubles event volume, and an overflowing "
                        "ring silently evicts mid-trace — the --events-out "
                        "meta line's dropped count says when to raise this")
    s.add_argument("--flight-out", default=None, metavar="FILE",
                   help="request-scoped flight tracing: write one JSONL "
                        "flight record per terminal (ordered stage "
                        "segments across both pools, hand-off links, "
                        "attribution self-check) here after the trace "
                        "drains (docs/OBSERVABILITY.md)")
    s.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome-trace/Perfetto JSON of the run "
                        "(one track per program pool, one async flow per "
                        "request, hand-off arrows) here after the trace "
                        "drains — open in https://ui.perfetto.dev or "
                        "chrome://tracing")
    s.add_argument("--blackbox", default=None, metavar="DIR",
                   help="arm the flight recorder: on a fatal drain or a "
                        "watchdog kill, dump a post-mortem bundle (span "
                        "ring tail, in-flight flight records, pool/queue "
                        "snapshot) into a numbered subdirectory of DIR")
    s.add_argument("--journal", default=None, metavar="FILE",
                   help="crash-safe request journal (append-only JSONL WAL, "
                        "fsync'd at batch boundaries); restarting against "
                        "the same file warm-restarts from the snapshot + "
                        "WAL tail: non-terminal requests replay exactly "
                        "once, already-resolved ids are deduped "
                        "(docs/SERVING.md#lifecycle)")
    s.add_argument("--snapshot-every-ms", type=float, default=None,
                   metavar="MS",
                   help="periodic journal snapshot+compaction on the "
                        "virtual clock (needs --journal): the replay-"
                        "folded state is written atomically next to the "
                        "WAL, the WAL rotates, and orphaned carry spills "
                        "are garbage-collected — restart cost becomes "
                        "O(traffic since the last snapshot)")
    s.add_argument("--drain-timeout-ms", type=float, default=None,
                   metavar="MS",
                   help="wall-clock budget for the graceful drain "
                        "(SIGTERM/Ctrl-C): past it the loop falls back to "
                        "snapshot-and-exit — journaled leftovers stay "
                        "pending for the warm restart, un-journaled ones "
                        "resolve to 'rejected' draining records "
                        "(default: unbounded)")
    s.add_argument("--chaos-plan", default=None, metavar="FILE",
                   help="deterministic fault-injection plan (JSON, see "
                        "p2p_tpu/serve/chaos.py; generator: tools/loadgen.py "
                        "--fault-rate). Drill tooling — never set this in "
                        "production")
    s.add_argument("--watchdog-ms", type=float, default=None, metavar="MS",
                   help="arm a wall-clock watchdog around each dispatched "
                        "batch: a compile/execute that hangs past this "
                        "deadline (with no step progress) becomes 'timeout' "
                        "records and a quarantined program-cache entry "
                        "instead of a wedged server")
    s.add_argument("--validate-outputs", action="store_true",
                   help="post-run finite check per lane (one jnp.isfinite "
                        "reduction off the hot path): NaN/Inf lanes resolve "
                        "to 'invalid_output' instead of shipping black "
                        "images")
    s.add_argument("--degrade-depth", type=int, default=None, metavar="N",
                   help="enable graceful degradation: when outstanding "
                        "work stays above N for --degrade-window-ms, the "
                        "loop steps down (force gate='auto' -> shrink max "
                        "bucket -> shed) before rejecting")
    s.add_argument("--degrade-window-ms", type=float, default=2000.0,
                   metavar="MS",
                   help="sustained-pressure window per degradation step "
                        "(and sustained-calm window per recovery step)")
    s.add_argument("--degrade-min-bucket", type=int, default=2,
                   choices=(1, 2, 4),
                   help="floor for the level-2 max-lane-bucket shrink")
    s.add_argument("--slo", action="store_true",
                   help="enable SLO-tiered multi-tenant scheduling: "
                        "requests carrying tenant/tier fields get "
                        "weighted-fair admission ordering, tier-pure "
                        "batches, tier-ordered dispatch, and per-tier "
                        "degradation (best-effort sheds first; premium "
                        "is exempt from the level-1 force-gate) — "
                        "docs/SERVING.md#slo-tiers-and-preemption")
    s.add_argument("--tenant-quota", type=int, default=None, metavar="N",
                   help="max outstanding requests per named tenant "
                        "(implies --slo); excess submissions reject with "
                        "the 'quota' kind")
    s.add_argument("--preempt-depth", type=int, default=None, metavar="N",
                   help="phase-boundary preemption (implies --slo): when "
                        "outstanding work exceeds N while higher-tier "
                        "work waits, lower-tier requests parked between "
                        "their phases spill their carry (journaled "
                        "'preempted' record) and resume when pressure "
                        "clears")
    s.add_argument("--cache", action="store_true",
                   help="enable content-addressed semantic caching "
                        "(ISSUE 13): requests are keyed by every output-"
                        "determining field and served from three layers — "
                        "text-encoder outputs, phase-1 carry prefixes "
                        "(a prefix hit enters the engine directly in "
                        "phase 2) and bitwise exact results with single-"
                        "flight collapsing of identical in-flight "
                        "requests. Off (the default), the record stream, "
                        "journal bytes and metric families are byte-"
                        "identical to the cache-less engine — "
                        "docs/SERVING.md#semantic-caching")
    s.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="spill directory for the cache's L2/L3 sidecar "
                        "files (content-addressed .npz; needs --cache; "
                        "default: a fresh tempdir). With --journal, "
                        "reusing the directory across restarts is what "
                        "lets a journaled insert serve followers after a "
                        "crash")
    s.add_argument("--cost", action="store_true",
                   help="enable the cost observatory (obs/costmodel.py, "
                        "docs/OBSERVABILITY.md): every program-cache miss "
                        "records an XLA cost card (flops, bytes, roofline "
                        "verdict, predicted ms) with compile_ms split into "
                        "build vs warm, every dispatch a measured-MFU "
                        "observation, and the summary gains a `cost` "
                        "block; per-request records are byte-identical "
                        "either way")
    s.add_argument("--programs-out", default=None, metavar="FILE",
                   help="write one JSON line per recorded program cost "
                        "card after the trace drains (implies --cost); "
                        "the artifact tools/perfscope.py --programs "
                        "renders")
    s.add_argument("--cache-l3-bytes", type=int, default=None, metavar="B",
                   help="in-memory byte budget for the exact-result layer "
                        "(LRU; eviction deletes the spill too; "
                        "default 256 MiB)")
    s.add_argument("--profile", default=None, metavar="DIR",
                   help="enable in-engine sampled device profiling "
                        "(ISSUE 18, docs/OBSERVABILITY.md#production-"
                        "profiling): every Nth dispatch (deterministic, "
                        "seeded, per-pool) runs under a programmatic "
                        "jax.profiler capture into a bounded trace ring "
                        "under DIR; captures fold into DIR/"
                        "workload_profile.json — the measured seed "
                        "artifact tools/schedule_search.py --profile and "
                        "tools/perfscope.py --sites consume — and EWMA "
                        "drift sentinels journal profile_drift events. "
                        "Off (the default), records, journal and "
                        "programs are byte-identical")
    s.add_argument("--profile-every", type=int, default=8, metavar="N",
                   help="sampling period: capture ~1 of every N "
                        "dispatches per pool (hash-mod on the seeded "
                        "plan, so the sampled set is reproducible; "
                        "default 8; 1 captures everything)")
    s.add_argument("--profile-seed", type=int, default=0, metavar="S",
                   help="sampling-plan seed (same seed => same sampled "
                        "dispatch set; default 0)")
    s.add_argument("--profile-ring-bytes", type=int, default=256 << 20,
                   metavar="B",
                   help="trace-ring size cap: oldest committed captures "
                        "are evicted past it (default 256 MiB)")
    s.add_argument("--profile-ring-count", type=int, default=16,
                   metavar="N",
                   help="trace-ring count cap (default 16 captures)")
    s.set_defaults(fn=cmd_serve)

    c = sub.add_parser(
        "check", help="checkpoint-readiness report (no weights loaded), "
                      "or --static: the jaxcheck static analyzer")
    c.add_argument("checkpoint_dir", nargs="?", default=None)
    c.add_argument("--preset", default=None,
                   choices=("sd14", "sd21", "sd21base", "ldm256", "sdxl",
                            "pixart_sigma"))
    c.add_argument("--static", action="store_true",
                   help="run the three-pass static analyzer instead (AST "
                        "lints + traced-program contracts + the "
                        "shardcheck collective-budget pass — "
                        "docs/STATIC_ANALYSIS.md); exits nonzero on new "
                        "findings or contract violations. Forces the "
                        "virtual 8-device CPU platform so the shardcheck "
                        "dp sweep matches the CI driver's. Full flag "
                        "surface (--only, --fix, --update-baseline): "
                        "tools/jaxcheck.py")
    c.add_argument("--ast-only", action="store_true",
                   help="with --static: skip the (slower) traced-program "
                        "and shardcheck passes")
    c.set_defaults(fn=cmd_check)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from .utils.cache import enable_persistent_cache

    enable_persistent_cache()
    try:
        return args.fn(args)
    except KeyboardInterrupt:
        # Ctrl-C outside a command's own graceful path (serve drains; see
        # cmd_serve) is a clean exit, never a raw traceback.
        print("interrupted", file=sys.stderr)
        return 130


if __name__ == "__main__":
    sys.exit(main())
