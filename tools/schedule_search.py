"""schedule_search — rehearsal-scale search for per-site reuse schedules.

Finds the fastest ``engine.reuse`` schedule that stays inside the golden
drift budget (ISSUE 15): a greedy per-site relaxation seeded by per-site
cost shares (perfscope's ``--sites`` table when given, else the analytic
per-site FLOP model — the same arithmetic the cost observatory's roofline
uses) and pruned by predicted saving, so compile time goes to the moves
that can actually pay.

    python tools/schedule_search.py                      # default search
    python tools/schedule_search.py --out tools/schedules/default_v1.json
    python tools/schedule_search.py --sites-json sites.json  # measured seed

The workload is the standard rehearsal replace-edit (the same trajectory
tests/test_phase_cache.py pins: 2-prompt edit, STEPS-step DDIM, seeded
latents) at ``--groups`` vmapped groups; drift is the latent MSE against
the in-session UNGATED baseline — the exact quantity the ≤1e-2 golden
budget bounds (quality_gate's ``schedule`` leg re-validates the committed
artifact against the same budget).

Search space (coarse by design — each distinct schedule is one XLA
compile):

1. CFG boundary sweep: ``cfg_gate`` over ``--gate-grid`` (kept at the
   first fraction whose drift fits — the PR-1 operating point).
2. Kind-level flip sweep: one shared reuse fraction for ALL self sites
   (A-SDM feature inheritance), then ALL cross sites earlier than the
   gate (TAD per-block redundancy), each descending ``--grid`` while the
   budget holds and wall time improves.
3. Per-site refinement: sites ordered by cost share (descending), each
   offered one-notch-earlier moves; accepted only if drift stays inside
   budget AND measured time does not regress. ``--prune`` skips sites
   whose predicted saving (share × steps saved) is below the threshold.

The emitted artifact records the measured speedup/drift and carries
``"*"`` defaults alongside the per-site entries, so one artifact serves
models whose layouts have different site counts (unknown site names are
inapplicable-by-design at resolve time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from p2p_tpu.utils.platform import force_cpu_platform  # noqa: E402

force_cpu_platform()

from p2p_tpu.utils.cache import default_cache_dir  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      default_cache_dir())


def site_cost_shares(layout, batch: int, seq: int = None) -> dict:
    """Analytic per-site cost share of one U-Net step — the roofline-model
    seed when no measured perfscope ``--sites`` table is given. Per
    attention site: q/k/v/out projections + the two attention matmuls,
    in FLOPs (2·m·n·k per matmul), normalized to sum 1 over all sites.
    The measured table (``tools/perfscope.py --sites``) uses the same
    site names, so the two seeds are interchangeable."""
    from p2p_tpu.engine.reuse import site_name

    shares = {}
    for m in layout.metas:
        p, c, k = m.pixels, m.channels, m.key_len
        # to_q: P×C×C; to_k/to_v: K×Cc×C (Cc unknown here — use C, the
        # share ordering is what matters); to_out: P×C×C; QKᵀ: P×K×C;
        # probs·V: P×K×C.
        flops = 2 * (p * c * c + 2 * k * c * c + p * c * c
                     + 2 * p * k * c)
        shares[site_name(m)] = float(flops * batch)
    total = sum(shares.values()) or 1.0
    return {k: v / total for k, v in shares.items()}


def standard_workload(pipe, steps: int, groups: int):
    """The rehearsal replace-edit workload: (ctx, lats, ctrls) for a
    ``groups``-wide sweep — the same trajectory family the phase-gate
    golden pins."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import encode_prompts
    from p2p_tpu.parallel import seed_latents

    prompts = ["a squirrel eating a burger", "a squirrel eating a lasagna"]
    ctrl = factory.attention_replace(
        prompts, steps, cross_replace_steps=0.4, self_replace_steps=0.25,
        tokenizer=pipe.tokenizer, self_max_pixels=8 * 8,
        max_len=pipe.config.text.max_length)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (groups,) + x.shape), ctrl)
    cond = encode_prompts(pipe, prompts)
    uncond = encode_prompts(pipe, [""] * len(prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)
    ctx = jnp.broadcast_to(ctx[None], (groups,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(42), groups, len(prompts),
                        pipe.latent_shape)
    return ctx, lats, ctrls, ctrl


class Evaluator:
    """Compile-and-measure one schedule spec on the standard workload.
    Counts evaluations (the search's cost unit) and memoizes by resolved
    table so grid moves that collapse to an already-measured schedule are
    free."""

    def __init__(self, pipe, steps: int, groups: int, reps: int = 3):
        import numpy as np

        self.pipe, self.steps, self.reps = pipe, steps, reps
        self.ctx, self.lats, self.ctrls, self.ctrl = standard_workload(
            pipe, steps, groups)
        self.evals = 0
        self._memo = {}
        base_lat, self.base_s = self._run_timed(None)
        self.base_lat = np.asarray(base_lat, np.float64)

    def _run_timed(self, spec):
        import jax

        from p2p_tpu.parallel.sweep import sweep

        def run():
            _, lat = sweep(self.pipe, self.ctx, self.lats, self.ctrls,
                           num_steps=self.steps, schedule=spec)
            jax.block_until_ready(lat)
            return lat

        lat = run()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(self.reps):
            run()
        return lat, (time.perf_counter() - t0) / self.reps

    def measure(self, spec) -> dict:
        """{'time_s', 'speedup', 'mse'} for one spec (memoized on the
        RESOLVED table — fraction/step spellings that coincide are one
        compile)."""
        import numpy as np

        from p2p_tpu.engine.reuse import resolve_schedule
        from p2p_tpu.models.config import unet_layout

        layout = unet_layout(self.pipe.config.unet)
        key = resolve_schedule(spec, layout, self._scan_steps(),
                               self.ctrl).key()
        if key in self._memo:
            return self._memo[key]
        self.evals += 1
        lat, t = self._run_timed(spec)
        mse = float(((np.asarray(lat, np.float64) - self.base_lat) ** 2)
                    .mean())
        out = {"time_s": t, "speedup": self.base_s / t, "mse": mse}
        self._memo[key] = out
        return out

    def _scan_steps(self) -> int:
        from p2p_tpu.ops import schedulers as sched_mod

        sched = sched_mod.schedule_from_config(
            self.steps, self.pipe.config.scheduler, kind="ddim")
        return int(sched.timesteps.shape[0])


def greedy_search(ev: Evaluator, layout, *, budget: float,
                  gate_grid, grid, prune: float, max_evals: int,
                  sites_shares: dict = None, log=print,
                  margin: float = 0.8) -> dict:
    """The search proper; returns {'spec', 'result', 'trail'}.

    ``margin``: schedules are accepted only under ``margin × budget`` —
    the committed artifact is re-validated against the FULL budget on
    every CI run, and a winner sitting 1% under it would make that leg a
    coin flip on any numeric-platform drift. The headroom is the
    search's, the budget is the gate's."""
    import warnings

    from p2p_tpu.engine.reuse import site_names

    shares = sites_shares or site_cost_shares(layout,
                                              batch=ev.ctx.shape[1])
    cross = list(site_names(layout, "cross"))
    selfs = list(site_names(layout, "self"))
    trail = []

    def try_spec(spec, label):
        if ev.evals >= max_evals:
            return None
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            r = ev.measure(spec)
        ok = r["mse"] <= margin * budget
        log(f"  {label:44s} speedup={r['speedup']:.3f} "
            f"mse={r['mse']:.2e} {'ok' if ok else 'OVER BUDGET'}")
        trail.append({"label": label, **r, "within_budget": ok})
        return r if ok else None

    # 1. CFG boundary: the coarsest, highest-leverage knob. A bare
    # cfg_gate IS the uniform gate (cross sites default to the boundary,
    # self sites to never).
    best_spec = {"cfg_gate": gate_grid[0]}
    best = try_spec(best_spec, f"uniform gate {gate_grid[0]}")
    if best is None:
        raise SystemExit(
            f"uniform gate {gate_grid[0]} already exceeds the drift "
            f"budget {budget} — no schedule can pass; raise --steps or "
            "the budget")
    for g in gate_grid[1:]:
        spec = {**best_spec, "cfg_gate": g}
        r = try_spec(spec, f"uniform gate {g}")
        if r is not None and r["speedup"] > best["speedup"]:
            best_spec, best = spec, r

    # 2. Kind-level flips: all self sites (A-SDM inheritance), then all
    # cross sites earlier than the boundary (TAD).
    for kind in ("self", "cross"):
        for frac in grid:
            spec = {**best_spec, kind: {"*": frac}}
            r = try_spec(spec, f"all-{kind} reuse @{frac}")
            if r is None:
                break   # drift grows monotonically down the grid
            if r["speedup"] >= best["speedup"]:
                best_spec, best = spec, r

    # 3. Per-site refinement, biggest predicted saving first; prune the
    # tail whose share can't pay for its compile.
    ordered = sorted(cross + selfs, key=lambda s: -shares.get(s, 0.0))
    for name in ordered:
        share = shares.get(name, 0.0)
        if share < prune:
            log(f"  pruned {name} (share {share:.3f} < {prune})")
            continue
        kind = "cross" if name.startswith("cross_attn/") else "self"
        table = dict(best_spec.get(kind) or {})
        current = table.get(name, table.get("*"))
        for frac in grid:
            if current is not None and frac >= current:
                continue
            spec = {**best_spec, kind: {**table, name: frac}}
            r = try_spec(spec, f"{name} @{frac}")
            if r is None or r["speedup"] < best["speedup"]:
                break
            best_spec, best = spec, r
            table = dict(best_spec[kind])
            current = frac
        if ev.evals >= max_evals:
            log(f"  eval budget {max_evals} reached")
            break

    return {"spec": best_spec, "result": best, "trail": trail}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=8,
                    help="rehearsal scan length (default 8, the "
                         "phase-gate golden's)")
    ap.add_argument("--groups", type=int, default=4,
                    help="vmapped edit groups in the timed sweep")
    ap.add_argument("--drift-budget", type=float, default=1e-2,
                    help="max latent MSE vs the ungated baseline (the "
                         "golden budget)")
    ap.add_argument("--gate-grid", default="0.5",
                    help="cfg_gate candidate fractions, best-first")
    ap.add_argument("--grid", default="0.75,0.62,0.5,0.44,0.38,0.31,0.25",
                    help="reuse-step candidate fractions, latest-first")
    ap.add_argument("--prune", type=float, default=0.01,
                    help="skip per-site refinement of sites whose "
                         "predicted cost share is below this")
    ap.add_argument("--margin", type=float, default=0.8,
                    help="accept only schedules under margin*budget — "
                         "headroom for the CI leg that re-validates the "
                         "artifact at the full budget")
    ap.add_argument("--max-evals", type=int, default=60,
                    help="hard cap on schedule compilations")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions per measurement")
    ap.add_argument("--sites-json", default=None, metavar="FILE",
                    help="measured per-site share table (the JSON "
                         "tools/perfscope.py --sites emits) to seed the "
                         "refinement order instead of the analytic model")
    ap.add_argument("--profile", default=None, metavar="LEDGER",
                    help="seed the refinement order from a serve "
                         "--profile WorkloadProfile ledger's measured "
                         "per-site shares (ISSUE 18: the engine-captured "
                         "equivalent of --sites-json — no hand-collected "
                         "trace). Mutually exclusive with --sites-json")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write the winning schedule artifact here")
    ap.add_argument("--preset", default="tiny",
                    help="model preset (tiny = the CI rehearsal scale)")
    args = ap.parse_args(argv)

    gate_grid = [float(x) for x in args.gate_grid.split(",") if x]
    grid = [float(x) for x in args.grid.split(",") if x]

    from p2p_tpu.models.config import PRESET_CONFIGS, unet_layout
    from tests.test_golden import _pipe

    cfg = PRESET_CONFIGS[args.preset]
    pipe = _pipe(cfg)
    layout = unet_layout(cfg.unet)

    if args.profile and args.sites_json:
        ap.error("--profile and --sites-json both seed the measured "
                 "share table — pass one")
    shares = None
    shares_src = None
    if args.sites_json:
        with open(args.sites_json) as f:
            data = json.load(f)
        shares = {e["site"]: e["share"] for e in data["sites"]}
        shares_src = args.sites_json
    elif args.profile:
        from p2p_tpu.obs import traceparse

        try:
            doc = traceparse.load_workload_profile(args.profile)
            entries = traceparse.profile_sites(doc)
        except (OSError, ValueError) as e:
            print(f"--profile: {e}", file=sys.stderr)
            return 2
        shares = {e["site"]: e["share"] for e in entries}
        shares_src = args.profile
    if shares is not None:
        print(f"seeded by measured shares: {shares_src} "
              f"({len(shares)} sites)")

    print(f"baseline: ungated {args.steps}-step replace edit, "
          f"{args.groups} groups")
    ev = Evaluator(pipe, args.steps, args.groups, reps=args.reps)
    print(f"  ungated {ev.base_s:.3f}s/run; searching "
          f"(budget mse<={args.drift_budget}, <= {args.max_evals} evals)")
    found = greedy_search(ev, layout, budget=args.drift_budget,
                          gate_grid=gate_grid, grid=grid, prune=args.prune,
                          max_evals=args.max_evals, sites_shares=shares,
                          margin=args.margin)

    r = found["result"]
    uniform = found["trail"][0]
    print(f"winner: speedup {r['speedup']:.3f}x (uniform gate "
          f"{uniform['speedup']:.3f}x), mse {r['mse']:.2e}, "
          f"{ev.evals} compile(s)")
    if args.out:
        spec = dict(found["spec"])
        spec["version"] = 1
        spec["provenance"] = {
            "tool": "tools/schedule_search.py",
            "preset": args.preset,
            "steps": args.steps,
            "groups": args.groups,
            "drift_budget": args.drift_budget,
            "measured_speedup": round(r["speedup"], 4),
            "uniform_gate_speedup": round(uniform["speedup"], 4),
            "measured_mse": r["mse"],
            "evals": ev.evals,
        }
        if shares_src is not None:
            spec["provenance"]["sites_source"] = shares_src
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(spec, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
