"""Pallas TPU kernels with in-kernel prompt-to-prompt editing.

The controller's map rewrites (Replace / Refine token remapping, Reweight
equalizers, self-attention injection) are structurally simple per-row
operations over the softmax probabilities — small matmuls and rescales along
the key axis. The materialized reference path
(`models/nn.py:attention_probs` → `controllers.base.apply_attention_control`)
pays a full ``(2B·heads, P, K)`` f32 HBM round-trip per edited site per step
for them; the kernels here apply the same algebra *inside* a tiled softmax,
so the probability tensor only ever exists as a ``(block_q, K)`` VMEM tile.

Layering: this package imports ``models.nn`` (block geometry) and
``controllers`` (edit semantics); ``models.unet`` imports this package for
site dispatch. Nothing here imports ``engine``.
"""

from ..controllers.kernel_spec import LANE
from .fused_edit import (
    edit_attention,
    edit_attention_reference,
    pad_to_lanes,
)
from .dispatch import (
    VARIANT_FLASH,
    VARIANT_FUSED,
    VARIANT_MATERIALIZED,
    VARIANT_USE,
    KernelConfig,
    site_variant,
)

__all__ = [
    "LANE",
    "KernelConfig",
    "VARIANT_FLASH",
    "VARIANT_FUSED",
    "VARIANT_MATERIALIZED",
    "VARIANT_USE",
    "edit_attention",
    "edit_attention_reference",
    "pad_to_lanes",
    "site_variant",
]
