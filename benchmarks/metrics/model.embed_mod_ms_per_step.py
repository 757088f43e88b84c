"""Model: device time a denoising step spends in a transformer denoiser outside
its attention sites and feed-forwards (``dit/patch_embed``, ``time_embed``,
``final`` and every ``dit/block<i>/modulate``: the patch embedding, the
timestep MLP, the adaLN-single modulation and the final layer), in ms. One of
the parts that sum to ``sampler.step_ms`` (``lib/scopes.py``); a U-Net has
no such scope, and reads 0."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("embed_mod") if scoped else None
