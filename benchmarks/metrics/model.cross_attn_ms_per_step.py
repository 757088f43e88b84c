"""Model: device time a denoising step spends under ``**/cross_attn/**``, the
prompt-to-prompt edit of the probabilities and the attention store included,
in ms. One of the parts that sum to ``sampler.step_ms`` (``lib/scopes.py``)."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("cross_attn") if scoped else None
