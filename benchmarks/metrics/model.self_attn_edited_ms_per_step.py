"""Model: device time a denoising step spends under ``self_attn/<site>/core``
of the self-attention sites the window's program ran as ``edited``: the
controller injects into them, or a reader takes their maps (scores and
softmax materialized, the injected map, a store's accumulation, P V), in ms.
Under the paper's edit with no reader of the store these are the six sites of
the two lowest levels (16 x 16 and 8 x 8 at SD-1.4, 24 x 24 and 12 x 12 at
SD-2.1); the class is read from the launch, not worked out
(``lib/self_sites.py``)."""

from benchmarks.lib import self_sites


def read(run):
    return self_sites.core_ms_per_step(run, "edited")
