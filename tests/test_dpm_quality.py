"""DPM-Solver++(2M) 20-step vs DDIM 50-step: the measured artifact behind
the bench's quality-matched operating point.

PERF.md's `dpm20_imgs_per_s` secondary claims DPM-Solver++ at 20 steps
reaches ~50-step-DDIM quality. The measurable core of that claim is solver
accuracy: both integrate the same probability-flow ODE, and quality is
formed where the x0-prediction varies smoothly in log-SNR λ (a trained
model's x0-pred is settled in the terminal high-λ phase). This module pins
that down with an analytically solvable problem run through the *actual*
`ddim_step` / `dpm_step` code:

* x0-prediction P(λ) = sin(λ), a pure function of λ — the exact solution is
  the quadrature  x_b = (σ_b/σ_a)·x_a + σ_b ∫ e^λ P(λ) dλ  (the identity
  DPM-Solver++ discretizes; one-step check: σ_n∫e^λdλ·P recovers the DDIM
  update exactly).
* Integrated over the *interior* interval t ∈ [100, 900] shared by every
  grid. The uniform-t ("leading") grid's final step spans λ ≈ 1.5 → 3.5 —
  a discretization limit common to ALL solvers on this grid (diffusers
  builds the same grid), measured and documented in PERF.md, not a solver
  property. Asserting through it would measure the grid, not the solver.

Measured result (committed as tests/golden/dpm_quality.json): DPM-20's
interior-trajectory error is an order of magnitude below DDIM-50's — at 20
steps the 2M solver exceeds 50-step DDIM accuracy everywhere the solution
is being formed, which is the precise sense in which the 1.71 img/s bench
secondary is "quality-matched".

``P2P_REGEN_GOLDEN=1 pytest tests/test_dpm_quality.py`` rewrites the JSON.
"""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.ops import schedulers as S

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "dpm_quality.json")

T_START, T_STOP = 900, 100  # grid points of every n used below


def _lam(a):
    return 0.5 * math.log(a / (1.0 - a))


# ∫ e^λ sin λ dλ in closed form: e^λ (sin λ − cos λ) / 2.
def _anti(l):
    return math.exp(l) * (math.sin(l) - math.cos(l)) / 2.0


def _solve(kind, n):
    """Integrate the analytic problem over [T_STOP, T_START] with the real
    sampler step functions, propagating the EXACT solution alongside (the
    homogeneous part is shared, so from any (λ_a, x_a) the truth is
    x_b = (σ_b/σ_a)·x_a + σ_b·(anti(λ_b) − anti(λ_a))). Returns the max
    per-step abs deviation from the exact trajectory — max-abs, not the
    signed endpoint difference, so oscillation-phase cancellation along
    sin(λ) cannot flatter a solver."""
    sched = S.make_schedule(n, kind="ddim")
    x = jnp.asarray([1.0])
    x_true = 1.0
    ms = S.init_dpm_state(x.shape)
    max_err = 0.0
    for t in np.asarray(sched.timesteps):
        if t > T_START or t - sched.step_size < T_STOP:
            continue
        a = float(S._alpha_at(sched, jnp.int32(t)))
        a_n = float(S._alpha_at(sched, jnp.int32(t - sched.step_size)))
        eps = (x - math.sqrt(a) * math.sin(_lam(a))) / math.sqrt(1.0 - a)
        if kind == "dpm":
            ms, x = S.dpm_step(sched, ms, eps, jnp.int32(t), x)
        else:
            x = S.ddim_step(sched, eps, jnp.int32(t), x)
        s_a, s_n = math.sqrt(1.0 - a), math.sqrt(1.0 - a_n)
        x_true = (s_n / s_a) * x_true + s_n * (_anti(_lam(a_n)) - _anti(_lam(a)))
        max_err = max(max_err, abs(float(x[0]) - x_true))
    return max_err


def test_dpm20_beats_ddim50_solver_accuracy():
    err = {f"{kind}{n}": _solve(kind, n)
           for kind, n in (("ddim", 20), ("ddim", 50),
                           ("dpm", 10), ("dpm", 20))}

    # The quality-matched claim, measured: 20-step DPM-Solver++ is at least
    # 3× more accurate than 50-step DDIM on the formed trajectory (measured
    # margin ~5.6×; 3× leaves platform-drift headroom). Even 10-step DPM
    # must beat 20-step DDIM.
    assert err["dpm20"] * 3 < err["ddim50"], err
    assert err["dpm10"] < err["ddim20"], err
    # Convergence sanity: DDIM order-1, DPM order-2 (monotone in steps —
    # the max-abs trajectory metric rules out endpoint cancellation).
    assert err["ddim50"] < err["ddim20"], err
    assert err["dpm20"] < err["dpm10"], err

    doc = {
        "problem": "x0-pred sin(lambda), interior interval t in [100, 900], "
                   "SD scaled_linear betas; metric: max per-step abs "
                   "deviation from the exact trajectory (antiderivative "
                   "reference propagated alongside)",
        "abs_error": {k: round(v, 8) for k, v in err.items()},
        "claim": "dpm20_error*3 < ddim50_error (measured margin ~5.6x); "
                 "dpm order-2 convergence visible: dpm10/dpm20 ~ 4.1x",
    }
    if os.environ.get("P2P_REGEN_GOLDEN"):
        with open(GOLDEN, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    assert os.path.exists(GOLDEN), (
        "committed artifact missing; regenerate with P2P_REGEN_GOLDEN=1")
    with open(GOLDEN) as f:
        committed = json.load(f)["abs_error"]
    for k, v in err.items():
        assert abs(committed[k] - v) <= 0.2 * max(v, 1e-6) + 1e-9, (
            f"committed artifact drifted at {k}: {committed[k]} vs {v:.8f}; "
            "regenerate with P2P_REGEN_GOLDEN=1 if intentional")


def test_terminal_lambda_jump_is_grid_not_solver():
    """Documentation-by-test for PERF.md: on the uniform-t grid the final
    step's λ-span is huge (≈2.0 at 20 steps) and identical for every
    solver — endpoint pointwise error there is a property of the grid.
    diffusers' DPMSolverMultistep builds the same 'leading' grid, so the
    reference's own DPM pipeline shares this limit."""
    sched = S.make_schedule(20, kind="ddim")
    ts = np.asarray(sched.timesteps)
    lam_spans = []
    for t in ts:
        a_t = float(S._alpha_at(sched, jnp.int32(t)))
        a_n = float(S._alpha_at(sched, jnp.int32(t - sched.step_size)))
        lam_spans.append(_lam(a_n) - _lam(a_t))
    # Final real step (t=step → 0) dominates every interior span by >4×.
    interior = lam_spans[:-2]
    assert lam_spans[-2] > 4 * max(interior), (lam_spans[-2], max(interior))
    # And the very last grid entry is the set_alpha_to_one=False no-op.
    assert lam_spans[-1] == pytest.approx(0.0, abs=1e-6)
