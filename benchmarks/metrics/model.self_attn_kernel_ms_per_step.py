"""Model: device time a denoising step spends under ``self_attn/<site>/core``
of the self-attention sites that ``nn.fused_attention`` sends to the flash
kernel (no controller reads them: the 64 x 64 sites of SD-1.4 and the 96 x 96
sites of SD-2.1 under the paper's edit), in ms: the kernel and the scaling
and layout copy of ``q`` fused ahead of it. With
``model.self_attn_stored_ms_per_step`` it accounts for
``model.self_attn_ms_per_step`` less the sites' ``qkv`` and ``out``
(``lib/self_sites.py``)."""

from benchmarks.lib import self_sites


def read(run):
    return self_sites.core_ms_per_step(run, "kernel")
