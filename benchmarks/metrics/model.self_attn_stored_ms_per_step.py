"""Model: device time a denoising step spends under ``self_attn/<site>/core``
of the self-attention sites the controller stores or edits (scores and
softmax materialized, the injected map, the store's accumulation, P V), in
ms: under the paper's edit every site up to half the latent's side, eleven of
sixteen (``lib/self_sites.py``)."""

from benchmarks.lib import self_sites


def read(run):
    return self_sites.core_ms_per_step(run, "stored")
