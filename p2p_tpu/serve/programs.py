"""LRU compiled-program cache for the serve loop.

A *program* here is a host-side runner bound to one ``(compile_key,
bucket)`` pair: a closure over the pipeline and every static sweep argument
(steps, scheduler, gate step, lane count). Building one warms it on
zero-valued inputs of the real batch's shapes — the XLA trace+compile (and
one cheap throwaway execution) happen at build time, so by the time real
lanes run the program, request latency is steady-state. The warm cost is
what the per-request ``compile_ms`` field reports.

The LRU evicts host handles only; the actual XLA executables additionally
live in the repo-wide persistent compile cache
(``utils.cache.default_cache_dir()``, enabled via
``utils.cache.enable_persistent_cache``), so re-building an evicted program
— or the same program in the next server process — is mostly disk I/O, not
a recompile. Counters (hits / misses / evictions) feed the per-request
records and the bench ``serve`` block.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Callable, Tuple

from ..obs import metrics as obs_metrics
from ..utils.cache import enable_persistent_cache


class ProgramCache:
    """LRU over built runners, keyed by ``(compile_key, bucket)``.

    ``retry_policy`` (a ``serve.faults.RetryPolicy``) wraps the build
    closure on miss: a *transient* build failure (device busy mid-compile,
    RESOURCE_EXHAUSTED) backs off on the wall clock and re-tries; poison/
    fatal failures propagate immediately. The serve engine passes its own
    policy here so prewarm and in-band compile misses share it — execution
    faults are still classified at dispatch and back off on the engine's
    *virtual* clock instead.

    :meth:`quarantine` handles the watchdog path: a program whose execution
    timed out is evicted and counted — the hang may have been the device,
    not the program, so a later miss is allowed to rebuild it, but never to
    reuse the possibly-wedged handle."""

    def __init__(self, capacity: int = 8, retry_policy=None):
        if capacity < 1:
            raise ValueError(f"program cache capacity must be >= 1, "
                             f"got {capacity}")
        enable_persistent_cache()
        self.capacity = capacity
        self.retry_policy = retry_policy
        self._lru: "OrderedDict[Tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.quarantined = 0
        self.build_retries = 0
        # Mirror of the instance counters in the process registry, so the
        # Prometheus snapshot carries cache behaviour without reaching into
        # the cache object (instance counters stay the record/bench source).
        self._m_events = obs_metrics.registry().counter(
            "serve_program_cache_events_total",
            "program-cache lookups and evictions by event",
            labels=("event",))

    def __len__(self) -> int:
        return len(self._lru)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._lru

    def get(self, key: Tuple, build: Callable[[], object]):
        """Return ``(runner, hit, build_ms)``; builds (and warms) on miss."""
        if key in self._lru:
            self._lru.move_to_end(key)
            self.hits += 1
            self._m_events.labels(event="hit").inc()
            return self._lru[key], True, 0.0
        self.misses += 1
        self._m_events.labels(event="miss").inc()
        t0 = time.perf_counter()
        if self.retry_policy is not None:
            from .faults import retry_call

            def _count_retry(attempt, delay_ms, exc):
                self.build_retries += 1
                self._m_events.labels(event="build_retry").inc()

            runner = retry_call(build, policy=self.retry_policy,
                                key=f"build:{key}", on_retry=_count_retry)
        else:
            runner = build()
        build_ms = (time.perf_counter() - t0) * 1000.0
        # Per-miss build/warm wall time into compile_ms{what="program"} —
        # the "where did this window's compile time go" decomposition.
        from ..obs import device as obs_device

        obs_device.record_compile(build_ms, what="program")
        self._lru[key] = runner
        while len(self._lru) > self.capacity:
            self._lru.popitem(last=False)
            self.evictions += 1
            self._m_events.labels(event="evict").inc()
        return runner, False, build_ms

    def quarantine(self, key: Tuple) -> bool:
        """Drop a suspect program (its execution timed out). Returns whether
        the key was held. Quarantine ≠ eviction in the stats: an eviction is
        capacity pressure, a quarantine is a health verdict."""
        held = self._lru.pop(key, None) is not None
        if held:
            self.quarantined += 1
            self._m_events.labels(event="quarantine").inc()
        return held

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._lru),
                "quarantined": self.quarantined,
                "build_retries": self.build_retries,
                "hit_rate": (self.hits / total) if total else 0.0}


def _reuse_from_key(sched_key: Tuple):
    """The reuse-schedule table from its compile-key component (or None):
    the runners rebuild the static table from the key alone, so identical
    tables from different request files build — and pool as — one
    program."""
    if sched_key is None:
        return None
    from ..engine.reuse import ReuseSchedule

    return ReuseSchedule.from_key(sched_key)


def _reuse_kwargs(gate_step, sched) -> dict:
    """The gate/schedule pair a runner's program was keyed for — mutually
    exclusive by construction (``resolve_reuse``), so exactly one is
    non-None. Shared by every runner class so the dispatch can never
    diverge between the monolithic and pool paths."""
    if sched is not None:
        return {"gate": None, "schedule": sched}
    return {"gate": gate_step, "schedule": None}


class SweepRunner:
    """Default runner: encode + stack + pad one batch, run ``parallel.sweep``.

    Encoding uses exactly the calls (and call shapes) ``text2image`` uses
    per request — cond and uncond encoded per request at the request's own
    prompt-batch size, latents drawn as ``normal(PRNGKey(seed))`` — so a
    lane's output is bitwise-identical to the direct path's for the same
    request (the quality-gate ``serve_parity`` contract).

    ``validate=True`` additionally reduces the final latents to one finite
    flag per lane (``engine.sampler.lane_finite`` — a separate tiny jitted
    program on the sweep's *output*, so the sweep program itself is
    untouched) and exposes it as ``last_lane_finite``; the engine converts
    non-finite lanes into ``invalid_output`` records instead of shipping
    the black images a NaN latent decodes to.
    """

    def __init__(self, pipe, compile_key: Tuple, bucket: int,
                 progress: bool = False, validate: bool = False,
                 heartbeat: bool = False, mesh=None, semcache=None):
        self.pipe = pipe
        (_, self.steps, self.scheduler, self.gate_step, self.group_batch,
         _, sched_key) = compile_key
        self.sched = _reuse_from_key(sched_key)
        self.bucket = bucket
        self.progress = progress
        self.validate = validate
        # ISSUE 13: the semantic cache's L1 layer — cond/uncond embeddings
        # are pure functions of (model, prompts), so repeated prompts skip
        # the text encoder. semcache=None (default) encodes every lane
        # exactly as before; a cached value is the same device array the
        # encoder produced, so reuse is bitwise by construction.
        self.semcache = semcache
        # A live jax.sharding.Mesh (or None): the sweep shards the lane
        # axis over its dp axis. Inputs are still assembled on the default
        # device; the sweep entry points stage them onto the mesh with
        # explicit NamedShardings (transfer-guard-clean either way).
        self.mesh = mesh
        # heartbeat=True traces the step callback in even when progress is
        # off (sweep's metrics flag: report=False, so nothing prints) —
        # the watchdog's liveness source must not depend on the operator
        # wanting progress lines (`--quiet --watchdog-ms` would otherwise
        # shoot every slow-but-alive in-band compile).
        self.heartbeat = heartbeat
        self.last_lane_finite = None

    def _inputs(self, entries, zeros: bool = False):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..engine.sampler import encode_prompts, init_latent, stage_host
        from ..models.conditioning import cfg_rows

        def encode(prompts):
            if self.semcache is None:
                return encode_prompts(self.pipe, list(prompts))
            return self.semcache.l1_get_or_build(
                (self.pipe.config.name,) + tuple(prompts),
                lambda: encode_prompts(self.pipe, list(prompts)))

        ctxs, lats, ctrls = [], [], []
        for e in entries:
            req = e.request
            cond = encode(req.prompts)
            uncond = encode(tuple([req.negative_prompt or ""]
                                  * len(req.prompts)))
            ctxs.append(cfg_rows(uncond, cond))
            # The seed is staged explicitly (np.int32 is exactly what
            # PRNGKey(int) resolves to under x64-off, so keys — and lanes —
            # stay bitwise-identical): PRNGKey(python_int) is an implicit
            # h2d transfer per lane, disallowed under the dispatch
            # transfer guard. Seeds outside int32 range keep the python-int
            # path — PRNGKey folds 64-bit ints natively, while np.int32
            # would raise (and an x64-off device stage would truncate).
            seed = (stage_host(np.int32(req.seed))
                    if -2**31 <= req.seed < 2**31 else req.seed)
            _, lat_b = init_latent(None, self.pipe.latent_shape,
                                   jax.random.PRNGKey(seed),
                                   len(req.prompts))
            lats.append(lat_b)
            ctrls.append(e.prepared.controller)
        while len(ctxs) < self.bucket:  # padding lanes replicate the last
            ctxs.append(ctxs[-1])
            lats.append(lats[-1])
            ctrls.append(ctrls[-1])
        ctx = jax.tree.map(lambda *xs: jnp.stack(xs), *ctxs)
        lat = jnp.stack(lats)
        ctrl = (None if ctrls[0] is None else
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls))
        if zeros:
            ctx, lat = jax.tree.map(jnp.zeros_like, (ctx, lat))
        return ctx, lat, ctrl

    def warm(self, entries) -> None:
        """Compile-ahead: run once on zero inputs of the batch's shapes.
        Shapes (not values) determine the program, so the real batch then
        executes warm — compile stays off the request path."""
        import jax

        ctx, lat, ctrl = self._inputs(entries, zeros=True)
        imgs, _ = self._run(ctx, lat, ctrl, guidance=1.0)
        jax.device_get(imgs)

    def cost_lowered(self, entries):
        """The cost observatory's build-time hook (``obs.costmodel``): the
        ``jax.stages.Lowered`` of this runner's exact program, built off
        the same zero inputs ``warm`` compiles with. ``.compile()`` on it
        yields the XLA cost/memory analysis for the program's cost card
        (lowered mesh-less — the card describes the logical computation;
        the scope scales peaks by device count)."""
        from ..parallel import sweep

        ctx, lat, ctrl = self._inputs(entries, zeros=True)
        return sweep(self.pipe, ctx, lat, ctrl, num_steps=self.steps,
                     guidance_scale=1.0, scheduler=self.scheduler,
                     mesh=None, **self._reuse_kw(),
                     progress=self.progress, metrics=self.heartbeat,
                     lower_only=True)

    def _reuse_kw(self) -> dict:
        return _reuse_kwargs(self.gate_step, self.sched)

    def _run(self, ctx, lat, ctrl, guidance: float):
        from ..parallel import sweep

        imgs, lats = sweep(self.pipe, ctx, lat, ctrl, num_steps=self.steps,
                           guidance_scale=guidance, scheduler=self.scheduler,
                           mesh=self.mesh, **self._reuse_kw(),
                           progress=self.progress, metrics=self.heartbeat)
        return imgs, lats

    def __call__(self, entries, guidance: float):
        # d2h via jax.device_get (never np.asarray): the whole call runs
        # transfer-guard-clean — every h2d is explicitly staged upstream
        # (tokens, schedule tables, guidance), and the two d2h fetches here
        # are the only host landings. tests/test_serve.py executes a steady-
        # state batch under jax.transfer_guard("disallow") to pin it.
        import jax

        ctx, lat, ctrl = self._inputs(entries)
        imgs, lats = self._run(ctx, lat, ctrl, guidance)
        if self.validate:
            from ..engine.sampler import lane_finite

            # Fetched eagerly so the engine's per-lane bool() check reads
            # host memory, not an implicit per-lane device sync.
            self.last_lane_finite = jax.device_get(lane_finite(lats))
        return jax.device_get(imgs)


_COND_HALF_JIT = None


def _cond_half(ctx, group_batch: int):
    """``ctx[:, group_batch:]`` as a compiled program with a static start
    index — transfer-free at execution, unlike the eager slice (whose
    ``dynamic_slice`` impl stages the start index h2d per call). One
    module-level jit wrapper so the program caches per (shape, start)."""
    global _COND_HALF_JIT
    if _COND_HALF_JIT is None:
        import functools

        import jax

        @functools.partial(jax.jit, static_argnames=("b",))
        def cut(x, b):
            return jax.tree.map(lambda leaf: leaf[:, b:], x)

        _COND_HALF_JIT = cut
    return _COND_HALF_JIT(ctx, b=group_batch)


class Phase1Runner(SweepRunner):
    """Phase-1 POOL runner: the same inputs as a monolithic sweep (CFG
    context halves, shared-seed latents, full controller), but the program
    runs only steps ``[0, gate)`` and returns the per-group
    :class:`~p2p_tpu.engine.sampler.PhaseCarry` (leaves with a leading
    ``bucket`` axis) instead of images — the hand-off units the engine
    splits per lane and feeds to the separately scheduled phase-2 pool."""

    def __init__(self, pipe, compile_key: Tuple, bucket: int,
                 progress: bool = False, validate: bool = False,
                 heartbeat: bool = False, mesh=None, semcache=None):
        # Strip the "phase1" pool tag; the rest is the monolithic key
        # layout SweepRunner already parses.
        super().__init__(pipe, compile_key[1:], bucket, progress=progress,
                         validate=validate, heartbeat=heartbeat, mesh=mesh,
                         semcache=semcache)

    def _run(self, ctx, lat, ctrl, guidance: float):
        from ..parallel.sweep import sweep_phase1

        return sweep_phase1(self.pipe, ctx, lat, ctrl, num_steps=self.steps,
                            guidance_scale=guidance,
                            scheduler=self.scheduler, mesh=self.mesh,
                            **self._reuse_kw(),
                            progress=self.progress, metrics=self.heartbeat)

    def cost_lowered(self, entries):
        from ..parallel.sweep import sweep_phase1

        ctx, lat, ctrl = self._inputs(entries, zeros=True)
        return sweep_phase1(self.pipe, ctx, lat, ctrl,
                            num_steps=self.steps, guidance_scale=1.0,
                            scheduler=self.scheduler, mesh=None,
                            **self._reuse_kw(), progress=self.progress,
                            metrics=self.heartbeat, lower_only=True)

    def warm(self, entries) -> None:
        import jax

        ctx, lat, ctrl = self._inputs(entries, zeros=True)
        jax.block_until_ready(self._run(ctx, lat, ctrl, guidance=1.0))

    def __call__(self, entries, guidance: float):
        import jax

        ctx, lat, ctrl = self._inputs(entries)
        carry = self._run(ctx, lat, ctrl, guidance)
        # The hand-off unit pairs the sampler carry with the already-
        # encoded cond context half, so phase 2 never re-runs the text
        # encoder for work phase 1 already did (and a journal-resumed
        # lane needs no encoder at all). Everything STAYS on device (only
        # a journal spill fetches it to host) — but the dispatch is
        # synchronized so run_ms measures execution, not async enqueue.
        # The cond half is cut by a jitted slice with a STATIC start: an
        # eager `ctx[:, b:]` stages its start index host→device on every
        # dispatch (dynamic_slice's eager impl), which the mesh
        # transfer-guard test caught in this previously-unguarded pool.
        return jax.block_until_ready(
            {"carry": carry, "ctx": _cond_half(ctx, self.group_batch)})


class Phase2Runner:
    """Phase-2 POOL runner: packs hand-off carries from *different*
    requests (different phase-1 batches, even different edit modes — the
    phase-2 compile key reduces the controller to what survives the gate)
    into one wide single-branch batch: steps ``[gate, S)`` off each lane's
    ``AttnCache`` + residual, then the VAE decode.

    Every lane's carry is validated against the request's pinned treedef
    spec (``engine.sampler.carry_spec`` vs :func:`handoff.carry_template`)
    before it touches the compiled program — a mismatched hand-off is a
    hard error at dispatch, not an XLA shape failure three layers down."""

    def __init__(self, pipe, compile_key: Tuple, bucket: int,
                 progress: bool = False, validate: bool = False,
                 heartbeat: bool = False, mesh=None, semcache=None):
        # semcache accepted for factory uniformity; phase 2 never encodes
        # (the hand-off unit already carries the cond context).
        self.pipe = pipe
        (_, _, self.steps, self.scheduler, self.gate_step, self.group_batch,
         _, sched_key) = compile_key
        # The phase-2 PROJECTION of the reuse table (phase2_view rode the
        # key): schedules differing only before the boundary share this
        # key — and therefore this program.
        self.sched = _reuse_from_key(sched_key)
        self.bucket = bucket
        self.progress = progress
        self.validate = validate
        self.heartbeat = heartbeat
        self.mesh = mesh
        self.last_lane_finite = None
        self._expected_spec = None

    def _spec_for(self, prep) -> str:
        import jax

        from ..engine.sampler import carry_spec

        from .handoff import carry_template

        if self._expected_spec is None:
            # Abstract evaluation only: the spec is a shape/dtype/treedef
            # string, so materializing the template's zero arrays here
            # would be pure waste — and its scalar constants would be
            # *implicit* h2d transfers inside the guarded dispatch path
            # (caught by the mesh transfer-guard test; carry_spec reads
            # shapes/dtypes identically off ShapeDtypeStructs).
            self._expected_spec = carry_spec(jax.eval_shape(
                lambda: carry_template(self.pipe, prep)))
        return self._expected_spec

    def _inputs(self, entries, zeros: bool = False):
        import jax
        import jax.numpy as jnp

        from ..engine.sampler import carry_spec, phase2_controller

        from .handoff import stack_carries

        carries, ctrls = [], []
        for e in entries:
            want = self._spec_for(e.prepared)
            got = carry_spec(e.carry)
            if got != want:
                raise ValueError(
                    f"hand-off carry for request {e.request_id!r} does not "
                    f"match its pinned treedef spec:\n  got  {got}\n"
                    f"  want {want}")
            carries.append(e.carry)
            ctrls.append(phase2_controller(e.prepared.controller))
        # Pack the hand-off units (sampler carry + encoded cond context)
        # into one phase-2 batch; padding replicates the last real lane.
        # On a mesh the lanes may live on different shards: stack_carries
        # reconciles them device-to-device (no host round-trip).
        packed = stack_carries(carries, self.bucket, mesh=self.mesh)
        ctx, carry = packed["ctx"], packed["carry"]
        while len(ctrls) < self.bucket:
            ctrls.append(ctrls[-1])
        ctrl = (None if ctrls[0] is None else
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls))
        if zeros:
            ctx, carry = jax.tree.map(jnp.zeros_like, (ctx, carry))
        return ctx, carry, ctrl

    def _reuse_kw(self) -> dict:
        return _reuse_kwargs(self.gate_step, self.sched)

    def _run(self, ctx, carry, ctrl, guidance: float):
        from ..parallel.sweep import sweep_phase2

        return sweep_phase2(self.pipe, ctx, carry, ctrl,
                            num_steps=self.steps, guidance_scale=guidance,
                            scheduler=self.scheduler, mesh=self.mesh,
                            **self._reuse_kw(),
                            progress=self.progress, metrics=self.heartbeat)

    def _template_inputs(self, entries):
        """Zero inputs shaped by the request alone
        (``handoff.carry_template``) — shared by :meth:`warm` (which must
        prewarm before any phase-1 batch has produced a real carry) and
        :meth:`cost_lowered` (whose card must describe that same
        program)."""
        import jax
        import jax.numpy as jnp

        from ..engine.sampler import phase2_controller

        from .handoff import carry_template

        prep = entries[0].prepared
        template = carry_template(self.pipe, prep)
        lead = jax.tree_util.tree_map(
            lambda x: jnp.zeros((self.bucket,) + tuple(x.shape), x.dtype),
            template)
        ctrl = phase2_controller(prep.controller)
        ctrl_g = (None if ctrl is None else jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * self.bucket), ctrl))
        return lead["ctx"], lead["carry"], ctrl_g

    def warm(self, entries) -> None:
        """Compile-ahead off zero inputs shaped by the request alone
        (``handoff.carry_template``), so the phase-2 program can prewarm
        before any phase-1 batch has produced a real carry."""
        import jax

        ctx, carry, ctrl_g = self._template_inputs(entries)
        imgs, _ = self._run(ctx, carry, ctrl_g, guidance=1.0)
        jax.device_get(imgs)

    def cost_lowered(self, entries):
        from ..parallel.sweep import sweep_phase2

        ctx, carry, ctrl_g = self._template_inputs(entries)
        return sweep_phase2(self.pipe, ctx, carry, ctrl_g,
                            num_steps=self.steps, guidance_scale=1.0,
                            scheduler=self.scheduler, mesh=None,
                            **self._reuse_kw(), progress=self.progress,
                            metrics=self.heartbeat, lower_only=True)

    def __call__(self, entries, guidance: float):
        import jax

        ctx, carry, ctrl = self._inputs(entries)
        imgs, lats = self._run(ctx, carry, ctrl, guidance)
        if self.validate:
            from ..engine.sampler import lane_finite

            self.last_lane_finite = jax.device_get(lane_finite(lats))
        return jax.device_get(imgs)


def default_runner_factory(pipe, progress: bool = False,
                           validate: bool = False, heartbeat: bool = False,
                           mesh=None, semcache=None):
    """The engine's default ``runner_factory``: real sweeps on ``pipe``.
    Dispatches on the compile key's pool tag — ``("phase1", ...)`` /
    ``("phase2", ...)`` keys build the disaggregated pool runners,
    everything else the monolithic :class:`SweepRunner` (ungated traffic's
    bitwise-unchanged fast path). ``mesh`` (a live ``jax.sharding.Mesh``)
    makes every runner dispatch sharded over its dp axis; the engine
    suffixes the cache key with the mesh shape (``serve.meshing.mesh_key``)
    — stripped here, since the runners parse the un-suffixed layout."""

    if mesh is not None:
        # Weight residency: replicate the sweep-side params onto the mesh
        # ONCE, so no dispatch ever pays (or implicitly performs) the
        # device-0 → mesh reshard. Shared by every runner the factory
        # builds.
        from .meshing import replicate_pipeline

        pipe = replicate_pipeline(pipe, mesh)

    def make(compile_key: Tuple, bucket: int):
        from .meshing import strip_mesh_key

        compile_key = strip_mesh_key(compile_key)
        kw = dict(progress=progress, validate=validate, heartbeat=heartbeat,
                  mesh=mesh, semcache=semcache)
        tag = compile_key[0] if compile_key else None
        if tag == "phase1":
            return Phase1Runner(pipe, compile_key, bucket, **kw)
        if tag == "phase2":
            return Phase2Runner(pipe, compile_key, bucket, **kw)
        return SweepRunner(pipe, compile_key, bucket, **kw)

    return make
