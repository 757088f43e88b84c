"""Model: device time a denoising step spends under ``**/self_attn/**``
(pre-norm, q/k/v, head split; scores, softmax, an injected map, P V, whichever
of XLA, the flash kernel or the fused kernel runs them; head merge, ``to_out``,
residual), in ms. One of the parts that sum to ``sampler.step_ms``
(``lib/scopes.py``)."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("self_attn") if scoped else None
