"""The denoiser behind the samplers, whichever its kind.

A preset's denoiser sits in ``PipelineConfig.unet`` and its tree in
``Pipeline.unet_params`` whatever it is: the conditional U-Net
(``models.unet``) or a transformer over patch tokens
(``models.transformer``), told apart by the configuration's ``kind``
(``"unet"`` where it has none). The sampling bodies call :func:`prepare`
once ahead of their scan and :func:`denoise` once a step; a U-Net gets
exactly the calls it always got, so its programs are the ones it had.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from ..obs import launches
from . import transformer, unet


def kind_of(cfg) -> str:
    """``"unet"`` or ``"transformer"``: the kind of a denoiser's
    configuration."""
    return getattr(cfg, "kind", "unet")


def require_unet(cfg, what: str) -> None:
    """Refuse ``what``, a path built on the U-Net alone, for a denoiser of
    any other kind, by its kind."""
    kind = kind_of(cfg)
    if kind != "unet":
        raise ValueError(f"{what}: not supported for a {kind} denoiser "
                         "(U-Net only)")


def init_denoiser(key: jax.Array, cfg):
    """A random-init tree for the denoiser's configuration."""
    if kind_of(cfg) == "transformer":
        return transformer.init_transformer(key, cfg)
    return unet.init_unet(key, cfg)


def prepare(params, cfg, cond):
    """``cond`` with what the denoiser reads of it that does not depend on
    the step filled in: a U-Net's added embedding (``unet.embed_added``), a
    transformer's projected caption keys and values
    (``transformer.embed_caption``)."""
    if kind_of(cfg) == "transformer":
        return transformer.embed_caption(params, cfg, cond)
    return unet.embed_added(params, cfg, cond)


def denoise(params, cfg, x, t, cond, controller=None, step=None, *,
            layout=None, state=(), sp=None, attn_cache=None,
            site_plan: Optional[Tuple[str, ...]] = None, kernels=None):
    """One forward of the denoiser: ``unet.apply_unet``'s arguments and
    results. A transformer takes no sequence-parallel plan, no fused-edit
    kernels and no cache plan (every site ``'off'``): those are the U-Net's
    alone, and each is refused here by the denoiser's kind."""
    kind = kind_of(cfg)
    launches.note_denoiser(kind)
    if kind == "unet":
        return unet.apply_unet(params, cfg, x, t, cond, layout=layout,
                               controller=controller, state=state, step=step,
                               sp=sp, attn_cache=attn_cache,
                               site_plan=site_plan, kernels=kernels)
    plan = site_plan or ()
    for name, given in (("sequence-parallel attention (sp)", sp is not None),
                        ("fused-edit kernels", kernels is not None),
                        ("cached attention sites (a gate or a reuse "
                         "schedule)", any(m != "off" for m in plan))):
        if given:
            require_unet(cfg, name)
    eps, state = transformer.apply_transformer(
        params, cfg, x, t, cond, layout=layout, controller=controller,
        state=state, step=step)
    if site_plan is not None:
        return eps, state, attn_cache
    return eps, state
