"""Model: device time a denoising step spends in the transformer blocks outside
attention (``**/attn*/ff``: the GEGLU feed-forward with its norm and residual;
``proj_in`` with the group norm before it; ``proj_out`` with the block's
residual), in ms. One of the parts that sum to ``sampler.step_ms``
(``lib/scopes.py``)."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("ff") if scoped else None
