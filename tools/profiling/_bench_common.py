"""Shared SD14 50-step scan benchmark, used by prof_flags.py and
prof_unroll.py. prof_experiments.py keeps its own inline copy because it
monkeypatches model internals between timings; prof_variants/prof_breakdown/
prof_gn_flash are frozen records of specific round-2 experiments."""
import os
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def require_accelerator():
    """Exit rather than time SD14 programs on the CPU backend: a profiling
    tool would print plausible-looking numbers that say nothing about the
    chip. P2P_PROF_ALLOW_CPU=1 overrides for anyone who really wants host
    timings."""
    import jax

    if (jax.devices()[0].platform == "cpu"
            and os.environ.get("P2P_PROF_ALLOW_CPU") != "1"):
        sys.exit("profiling refused: jax backend is cpu; set "
                 "P2P_PROF_ALLOW_CPU=1 to time the host")


def sd14_scan_ms_per_step(batch: int = 4, steps: int = 50, repeats: int = 2,
                          compiler_options=None, unroll: int = 1) -> float:
    """Best-of-N ms/step for the jitted SD14 U-Net scan (identity controller).

    ``compiler_options`` are forwarded to ``jax.jit`` (per-program
    ``xla_tpu_*`` options). ``unroll`` is forwarded to ``lax.scan``."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.models import SD14, init_unet, unet_layout
    from p2p_tpu.models.unet import apply_unet
    from p2p_tpu.utils.cache import enable_persistent_cache

    require_accelerator()
    enable_persistent_cache()

    cfg = SD14
    layout = unet_layout(cfg.unet)
    params = init_unet(jax.random.PRNGKey(0), cfg.unet)
    s = cfg.latent_size
    x = jnp.ones((batch, s, s, cfg.unet.in_channels), jnp.bfloat16)
    ctx = jnp.ones((batch, cfg.unet.context_len, cfg.unet.context_dim),
                   jnp.bfloat16)

    @partial(jax.jit, compiler_options=compiler_options)
    def scan(params, x, ctx):
        def body(h, t):
            eps, _ = apply_unet(params, cfg.unet, h, t, ctx, layout=layout)
            return eps, None
        out, _ = jax.lax.scan(body, x, jnp.arange(steps, dtype=jnp.int32),
                              unroll=unroll)
        return out

    jax.block_until_ready(scan(params, x, ctx))  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(scan(params, x, ctx))
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1000.0
