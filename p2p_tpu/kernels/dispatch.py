"""Static kernel dispatch: which attention variant each site lowers to.

Dispatch is decided entirely at trace time from four static inputs — the
:class:`KernelConfig`, the controller structure, the site's ``AttnMeta``,
and the site's reuse-schedule mode for the current scan segment — so every
distinct (config, plan) pair is still ONE compiled program, mirroring how
``engine.reuse.segments`` already cuts the scan into constant-plan
``lax.scan`` segments:

=================  =========================================================
variant            lowering
=================  =========================================================
``use``            no attention math at all — the site serves its AttnCache
                   leaf (the fused "side-input": the cached tensor IS the
                   kernel-output representation a store segment emitted)
``flash``          plain fused attention (``models.nn.fused_attention``:
                   the library flash kernel at flash-tileable geometry) —
                   untouched sites, including ``store``/``store_all``
                   segments, whose cache capture is the site output the
                   kernel already produces (the fused "side-output")
``fused-edit``     the in-kernel edit program (``kernels.fused_edit``)
``materialized``   the reference f32 path — controller-touched sites the
                   kernel cannot express (attention-store sites) or that
                   the config doesn't cover
=================  =========================================================
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple, Union

from ..controllers.base import AttnMeta, Controller, controller_touches
from ..controllers.kernel_spec import kernel_edit_spec

VARIANT_USE = "use"
VARIANT_FLASH = "flash"
VARIANT_FUSED = "fused-edit"
VARIANT_MATERIALIZED = "materialized"


def site_name(meta: AttnMeta) -> str:
    """Canonical site vocabulary — one definition (engine.reuse)."""
    from ..engine.reuse import site_name as _site_name

    return _site_name(meta)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Static fused-kernel dispatch plan (hashable — rides jit static args).

    ``sites``: ``"*"`` fuses every kernel-compilable site; a tuple of site
    names (``cross_attn/down3`` …) restricts fusion to those — the ordered
    fuse-first list ``tools/perfscope.py --fuse-plan`` emits. ``block_q=0``
    lets ``models.nn.edit_block`` pick the query tile per site geometry.
    ``interpret`` runs the kernels through the pallas interpreter — the CPU
    rehearsal/parity surface; on-chip runs leave it False."""

    sites: Union[str, Tuple[str, ...]] = "*"
    block_q: int = 0
    interpret: bool = False

    def __post_init__(self):
        if self.sites != "*" and not isinstance(self.sites, tuple):
            raise ValueError(
                f"KernelConfig.sites must be '*' or a tuple of site names, "
                f"got {self.sites!r}")

    def covers(self, name: str) -> bool:
        return self.sites == "*" or name in self.sites

    @classmethod
    def from_fuse_plan(cls, plan: Union[str, dict], take: Optional[int] = None,
                       **kwargs) -> "KernelConfig":
        """Build a config from a ``perfscope --fuse-plan`` artifact (a path
        or the loaded dict): take the top ``take`` sites of the ranked
        fuse-first order (all of them by default)."""
        if isinstance(plan, str):
            with open(plan) as f:
                plan = json.load(f)
        order = [entry["site"] for entry in plan["fuse_order"]]
        if take is not None:
            order = order[:take]
        return cls(sites=tuple(order), **kwargs)


def site_variant(kernels: Optional[KernelConfig],
                 controller: Optional[Controller],
                 meta: AttnMeta, mode: str) -> str:
    """The static attention variant for one site in one scan segment.
    ``mode`` is the site's reuse-schedule action (``engine.reuse`` MODE_*,
    the entries of ``apply_unet``'s ``site_plan``)."""
    if mode == "use":
        return VARIANT_USE
    if not controller_touches(controller, meta):
        return VARIANT_FLASH
    if (kernels is not None and kernels.covers(site_name(meta))
            and kernel_edit_spec(controller, meta) is not None):
        return VARIANT_FUSED
    return VARIANT_MATERIALIZED
