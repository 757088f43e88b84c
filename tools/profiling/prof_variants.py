"""Time SD14 50-step sampling variants on the real TPU chip.

Variants isolate the cost components:
  identity     — no controller: all sites fused (model ceiling)
  edit_store   — AttentionReplace, store=True (current bench default)
  edit_nostore — AttentionReplace, store=False
"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from p2p_tpu.controllers import factory
from p2p_tpu.engine.sampler import Pipeline, text2image
from p2p_tpu.models import SD14, init_text_encoder, init_unet
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.utils.tokenizer import HashWordTokenizer

# Siblings insert the script dir explicitly: when a launcher runs this file
# by absolute path from another cwd with an inherited sys.path[0], the
# implicit script-dir entry is not guaranteed — the _bench_common import
# must not depend on it.
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_common import require_accelerator

require_accelerator()

NUM_STEPS = 50
cfg = SD14
tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
pipe = Pipeline(
    config=cfg,
    unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
    text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
    vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
    tokenizer=tok,
)
prompts = ["a squirrel eating a burger", "a squirrel eating a lasagna"]

def ctrl(store):
    return factory.attention_replace(
        prompts, NUM_STEPS, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=16 * 16, max_len=cfg.text.max_length,
        store=store)

variants = {
    "identity": None,
    "edit_store": ctrl(True),
    "edit_nostore": ctrl(False),
}

for name, controller in variants.items():
    def run(seed):
        img, _, _ = text2image(pipe, prompts, controller, num_steps=NUM_STEPS,
                               rng=jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
        return np.asarray(img)
    t0 = time.perf_counter()
    run(0)
    compile_s = time.perf_counter() - t0
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        run(i + 1)
        times.append(time.perf_counter() - t0)
    best = min(times)
    print(f"{name:13s} compile {compile_s:6.1f}s  best {best*1000:8.1f} ms "
          f"-> {2/best:6.3f} img/s  ({best/NUM_STEPS*1000:6.2f} ms/step incl VAE)",
          flush=True)
