"""Model: device time a denoising step spends in the U-Net's ResNet blocks and
what stands with them (``unet/<place>/res*``, ``conv_in``, ``conv_out``,
``time_embed``, the up- and down-samplers, ``skip_concat``), in ms: the
loop's leaf operations of the traced window joined by instruction name to
the program's scope index (``lib/scopes.py``). One of the parts that sum
to ``sampler.step_ms``."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.loop_ms_per_step("resblock") if scoped else None
