"""Scheduler numerics: closed-form oracles, inversion round-trips, exact-noise
recovery, and a list-based PLMS simulator oracle."""

import jax
import jax.numpy as jnp
import numpy as np

from p2p_tpu.ops.schedulers import (
    DiffusionSchedule,
    schedule_from_config,
    add_noise,
    ddim_next_step,
    ddim_step,
    ddpm_step,
    init_plms_state,
    make_betas,
    make_schedule,
    plms_step,
)


def test_betas_scaled_linear_endpoints():
    b = make_betas()
    assert abs(b[0] - 0.00085) < 1e-12
    assert abs(b[-1] - 0.012) < 1e-12
    assert b.shape == (1000,)


def test_ddim_timesteps_descend_and_offset():
    s = make_schedule(50)
    ts = np.asarray(s.timesteps)
    assert ts[0] == 980 and ts[-1] == 0 and len(ts) == 50
    s1 = make_schedule(50, steps_offset=1)
    assert np.asarray(s1.timesteps)[0] == 981


def test_plms_timesteps_repeat_second():
    s = make_schedule(50, kind="plms")
    ts = np.asarray(s.timesteps)
    assert len(ts) == 51
    assert ts[0] == 980 and ts[1] == 960 and ts[2] == 960 and ts[3] == 940


def test_ddim_zero_eps_scales_by_alpha_ratio():
    s = make_schedule(50)
    x = jnp.ones((2, 4, 4, 1))
    t = jnp.int32(980)
    out = ddim_step(s, jnp.zeros_like(x), t, x)
    a_t = s.alphas_cumprod[980]
    a_prev = s.alphas_cumprod[960]
    np.testing.assert_allclose(np.asarray(out), np.sqrt(a_prev / a_t), rtol=1e-5)


def test_ddim_final_step_uses_final_alpha():
    s = make_schedule(50, set_alpha_to_one=False)
    x = jnp.full((1, 2, 2, 1), 0.7)
    out = ddim_step(s, jnp.zeros_like(x), jnp.int32(0), x)
    a_t = s.alphas_cumprod[0]
    # prev_t = -20 < 0 -> final_alpha_cumprod = alphas_cumprod[0] = a_t
    np.testing.assert_allclose(np.asarray(out), 0.7 * np.sqrt(a_t / a_t), rtol=1e-6)


def test_ddim_matches_reference_closed_form():
    """Independent transcription of /root/reference/null_text.py:471-489."""
    s = make_schedule(50)
    acp = np.asarray(s.alphas_cumprod, dtype=np.float64)
    rng = np.random.RandomState(0)
    x = rng.randn(1, 4, 4, 2).astype(np.float32)
    eps = rng.randn(1, 4, 4, 2).astype(np.float32)
    for t in [980, 500, 20]:
        prev_t = t - 20
        a_t, a_prev = acp[t], (acp[prev_t] if prev_t >= 0 else acp[0])
        x0 = (x - (1 - a_t) ** 0.5 * eps) / a_t ** 0.5
        want = a_prev ** 0.5 * x0 + (1 - a_prev) ** 0.5 * eps
        got = ddim_step(s, jnp.asarray(eps), jnp.int32(t), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-5)
        # next_step: timestep pair (t-20 -> t)
        cur_t = min(t - 20, 999)
        a_c = acp[cur_t] if cur_t >= 0 else acp[0]
        a_n = acp[t]
        x0n = (x - (1 - a_c) ** 0.5 * eps) / a_c ** 0.5
        wantn = a_n ** 0.5 * x0n + (1 - a_n) ** 0.5 * eps
        gotn = ddim_next_step(s, jnp.asarray(eps), jnp.int32(t), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(gotn), wantn, rtol=2e-4, atol=1e-5)


def test_ddim_inversion_roundtrip():
    """next_step then prev_step with the same eps is identity (closed forms
    are exact inverses when eps is held fixed)."""
    s = make_schedule(50)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 8, 8, 4).astype(np.float32))
    eps = jnp.asarray(rng.randn(1, 8, 8, 4).astype(np.float32))
    t = jnp.int32(500)
    up = ddim_next_step(s, eps, t, x)
    down = ddim_step(s, eps, t, up)
    np.testing.assert_allclose(np.asarray(down), np.asarray(x), rtol=1e-3, atol=1e-4)


def test_ddim_exact_noise_recovers_x0():
    """If the model predicts the exact noise consistent with x_t, the DDIM
    chain lands on x0 when set_alpha_to_one=True; with the SD setting
    (False) it terminates at the t=0 noise level, sqrt(1-acp[0]) above x0."""
    for alpha_to_one in (True, False):
        s = make_schedule(50, set_alpha_to_one=alpha_to_one)
        rng = np.random.RandomState(2)
        x0 = jnp.asarray(rng.randn(1, 4, 4, 1).astype(np.float32))
        noise = jnp.asarray(rng.randn(1, 4, 4, 1).astype(np.float32))
        x = add_noise(s, x0, noise, jnp.int32(980))

        def eps_of(x, t):
            a = s.alphas_cumprod[t]
            return (x - jnp.sqrt(a) * x0) / jnp.sqrt(1.0 - a)

        for t in np.asarray(s.timesteps):
            x = ddim_step(s, eps_of(x, int(t)), jnp.int32(int(t)), x)
        if alpha_to_one:
            np.testing.assert_allclose(np.asarray(x), np.asarray(x0), rtol=1e-2, atol=1e-3)
        else:
            a0 = np.asarray(s.alphas_cumprod[0])
            want = np.sqrt(a0) * np.asarray(x0) + np.sqrt(1 - a0) * np.asarray(noise)
            np.testing.assert_allclose(np.asarray(x), want, rtol=1e-2, atol=1e-3)


class PlmsSimulator:
    """List-based PLMS oracle following Liu et al. (arXiv 2202.09778) with the
    warm-up re-evaluation, written independently of the scan implementation."""

    def __init__(self, acp, step):
        self.acp = acp
        self.step = step
        self.ets = []
        self.counter = 0
        self.cur_sample = None

    def phi(self, x, t, prev_t, eps):
        a_t = self.acp[t] if t >= 0 else self.acp[0]
        a_p = self.acp[prev_t] if prev_t >= 0 else self.acp[0]
        denom = a_t * (1 - a_p) ** 0.5 + (a_t * (1 - a_t) * a_p) ** 0.5
        return (a_p / a_t) ** 0.5 * x - (a_p - a_t) * eps / denom

    def __call__(self, eps, t, x):
        prev_t = t - self.step
        if self.counter != 1:
            self.ets.append(eps)
        else:
            prev_t = t
            t = t + self.step
        if len(self.ets) == 1 and self.counter == 0:
            used = eps
            self.cur_sample = x
        elif len(self.ets) == 1 and self.counter == 1:
            used = (eps + self.ets[-1]) / 2
            x = self.cur_sample
        elif len(self.ets) == 2:
            used = (3 * self.ets[-1] - self.ets[-2]) / 2
        elif len(self.ets) == 3:
            used = (23 * self.ets[-1] - 16 * self.ets[-2] + 5 * self.ets[-3]) / 12
        else:
            used = (55 * self.ets[-1] - 59 * self.ets[-2] + 37 * self.ets[-3]
                    - 9 * self.ets[-4]) / 24
        self.counter += 1
        return self.phi(x, t, prev_t, used)


def test_plms_matches_list_simulator():
    T = 10
    s = make_schedule(T, kind="plms")
    acp = np.asarray(s.alphas_cumprod, dtype=np.float64)
    rng = np.random.RandomState(3)
    x0 = rng.randn(1, 4, 4, 1).astype(np.float64)

    def model(x, t):
        # a smooth, state-dependent fake ε so multistep history matters
        return 0.3 * x + 0.01 * t / 1000.0

    sim = PlmsSimulator(acp, s.step_size)
    x_sim = x0.copy()
    for t in np.asarray(s.timesteps):
        x_sim = sim(model(x_sim, int(t)), int(t), x_sim)

    state = init_plms_state(x0.shape)
    x_jax = jnp.asarray(x0.astype(np.float32))
    for t in np.asarray(s.timesteps):
        eps = jnp.asarray(model(np.asarray(x_jax, dtype=np.float64), int(t)).astype(np.float32))
        state, x_jax = plms_step(s, state, eps, jnp.int32(int(t)), x_jax)
    np.testing.assert_allclose(np.asarray(x_jax), x_sim, rtol=5e-3, atol=1e-4)


def test_plms_scan_compatible():
    T = 5
    s = make_schedule(T, kind="plms")
    x0 = jnp.ones((1, 2, 2, 1))

    def body(carry, t):
        state, x = carry
        eps = 0.1 * x
        state, x = plms_step(s, state, eps, t, x)
        return (state, x), None

    (state, x), _ = jax.lax.scan(body, (init_plms_state(x0.shape), x0), s.timesteps)
    assert np.isfinite(np.asarray(x)).all()
    assert int(state.counter) == T + 1


def test_ddpm_terminal_step_is_mean_only():
    s = make_schedule(50)
    x = jnp.ones((1, 2, 2, 1))
    out1 = ddpm_step(s, jnp.zeros_like(x), jnp.int32(0), x, jax.random.PRNGKey(0))
    out2 = ddpm_step(s, jnp.zeros_like(x), jnp.int32(0), x, jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_add_noise_interpolates():
    s = make_schedule(50)
    x0 = jnp.ones((1, 2, 2, 1))
    n = jnp.zeros_like(x0)
    out = add_noise(s, x0, n, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out), np.sqrt(np.asarray(s.alphas_cumprod[0])), rtol=1e-6)


# ---------------------------------------------------------------------------
# SchedulerConfig parity: the constants diffusers' SD
# PNDM / DDIM configs produce, hand-derived from their documented formulas
# (`/root/reference/main.py:29` pipeline PNDM has steps_offset=1;
# `/root/reference/null_text.py:16-20` DDIM has offset 0, clip_sample=False).
# ---------------------------------------------------------------------------


def test_sd_plms_schedule_has_steps_offset_1():
    from p2p_tpu.models.config import SD14

    s = schedule_from_config(50, SD14.scheduler, kind="plms")
    ts = np.asarray(s.timesteps)
    # diffusers PNDM (skip_prk): base = arange(50)*20 + 1; plms layout
    # duplicates the second-highest then reverses -> [981, 961, 961, 941, ...]
    base = np.arange(50) * 20 + 1
    want = np.concatenate([base[:-1], base[-2:-1], base[-1:]])[::-1]
    assert ts.tolist() == want.tolist()
    assert ts[0] == 981 and ts[1] == 961 and ts[2] == 961 and ts[-1] == 1


def test_sd_ddim_schedule_has_steps_offset_0():
    from p2p_tpu.models.config import SD14

    s = schedule_from_config(50, SD14.scheduler, kind="ddim")
    ts = np.asarray(s.timesteps)
    assert ts.tolist() == list(range(980, -1, -20))
    assert not s.clip_sample
    # set_alpha_to_one=False: final alpha is alphas_cumprod[0] = 1 - 0.00085.
    np.testing.assert_allclose(float(s.final_alpha_cumprod), 1.0 - 0.00085,
                               rtol=1e-6)


def test_ldm_schedule_constants():
    from p2p_tpu.models.config import LDM256

    s = schedule_from_config(50, LDM256.scheduler, kind="ddim")
    betas = make_betas(1000, LDM256.scheduler.beta_start,
                             LDM256.scheduler.beta_end)
    np.testing.assert_allclose(betas[0], 0.0015, rtol=1e-6)
    np.testing.assert_allclose(betas[-1], 0.0195, rtol=1e-6)
    np.testing.assert_allclose(float(s.alphas_cumprod[0]), 1 - 0.0015, rtol=1e-6)


def test_clip_sample_clamps_pred_x0():
    s = make_schedule(10, clip_sample=True)
    s_off = make_schedule(10, clip_sample=False)
    x = jnp.full((1, 2, 2, 1), 30.0)  # huge sample -> pred_x0 way outside [-1,1]
    eps = jnp.zeros_like(x)
    t = s.timesteps[0]
    on = np.asarray(ddim_step(s, eps, t, x))
    off = np.asarray(ddim_step(s_off, eps, t, x))
    a_prev = float(s.alphas_cumprod[int(t) - s.step_size])
    # with eps=0 and clipping, the update is exactly sqrt(a_prev) * 1.0
    np.testing.assert_allclose(on, np.sqrt(a_prev), rtol=1e-5)
    assert np.all(off > 10.0)


# ---------------------------------------------------------------------------
# DPM-Solver++(2M) — list-based oracle + exactness checks
# ---------------------------------------------------------------------------


class DpmSimulator:
    """Independent list-based DPM-Solver++(2M) (Lu et al., arXiv 2211.01095),
    data-prediction form with lower-order final step."""

    def __init__(self, acp, step, final_alpha):
        self.acp = acp
        self.step = step
        self.final = final_alpha
        self.x0s = []
        self.lams = []

    def _consts(self, t):
        a = self.acp[t] if t >= 0 else self.final
        alpha, sigma = np.sqrt(a), np.sqrt(1 - a)
        return alpha, sigma, np.log(alpha / sigma)

    def __call__(self, eps, t, x):
        prev_t = t - self.step
        al_t, sg_t, lam_t = self._consts(t)
        al_n, sg_n, lam_n = self._consts(prev_t)
        h = lam_n - lam_t
        x0 = (x - sg_t * eps) / al_t
        if self.x0s and prev_t >= 0:
            h_prev = lam_t - self.lams[-1]
            r = h_prev / h
            d = (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * self.x0s[-1]
        else:
            d = x0
        self.x0s.append(x0)
        self.lams.append(lam_t)
        return (sg_n / sg_t) * x - al_n * np.expm1(-h) * d


def test_dpm_matches_list_simulator():
    from p2p_tpu.ops.schedulers import DpmState, dpm_step, init_dpm_state

    T = 8
    s = make_schedule(T, kind="dpm")
    acp = np.asarray(s.alphas_cumprod, dtype=np.float64)
    rng = np.random.RandomState(5)
    x0 = rng.randn(1, 4, 4, 1)

    def model(x, t):
        return 0.2 * x + 0.05 * t / 1000.0

    sim = DpmSimulator(acp, s.step_size, float(s.final_alpha_cumprod))
    x_sim = x0.copy()
    for t in np.asarray(s.timesteps):
        x_sim = sim(model(x_sim, int(t)), int(t), x_sim)

    state = init_dpm_state(x0.shape)
    x_jax = jnp.asarray(x0.astype(np.float32))
    for t in np.asarray(s.timesteps):
        eps = jnp.asarray(model(np.asarray(x_jax, np.float64), int(t))
                          .astype(np.float32))
        state, x_jax = dpm_step(s, state, eps, jnp.int32(int(t)), x_jax)
    np.testing.assert_allclose(np.asarray(x_jax), x_sim, rtol=5e-4, atol=1e-5)


def test_dpm_exact_noise_recovers_x0():
    """With the model predicting the exact consistent noise, DPM-Solver++
    lands on x0's terminal noise level just like DDIM (both integrate the
    same probability-flow ODE exactly for this linear case)."""
    from p2p_tpu.ops.schedulers import dpm_step, init_dpm_state

    s = make_schedule(25, kind="dpm")
    rng = np.random.RandomState(6)
    x0 = jnp.asarray(rng.randn(1, 4, 4, 1).astype(np.float32))
    noise = jnp.asarray(rng.randn(1, 4, 4, 1).astype(np.float32))
    x = add_noise(s, x0, noise, jnp.int32(980))

    def eps_of(x, t):
        a = s.alphas_cumprod[t]
        return (x - jnp.sqrt(a) * x0) / jnp.sqrt(1.0 - a)

    state = init_dpm_state(x0.shape)
    for t in np.asarray(s.timesteps):
        state, x = dpm_step(s, state, eps_of(x, int(t)), jnp.int32(int(t)), x)
    a0 = np.asarray(s.alphas_cumprod[0])
    want = np.sqrt(a0) * np.asarray(x0) + np.sqrt(1 - a0) * np.asarray(noise)
    np.testing.assert_allclose(np.asarray(x), want, rtol=5e-2, atol=5e-3)


def test_dpm_e2e_smoke(tiny_pipe):
    """scheduler='dpm' runs end-to-end under an edit controller."""
    import jax as _jax

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import text2image

    prompts = ["a cat on a mat", "a dog on a mat"]
    ctrl = factory.attention_replace(
        prompts, 3, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tiny_pipe.tokenizer, self_max_pixels=8 * 8,
        max_len=tiny_pipe.config.text.max_length)
    img, _, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=3,
                           scheduler="dpm", rng=_jax.random.PRNGKey(0))
    assert img.shape[0] == 2
    assert np.isfinite(np.asarray(img, np.float32)).all()
