"""The self-attention sites of a cell by who runs them, worked out from the
configuration's layout and the traffic mix's edit, and the loop's device time
under each class's ``self_attn/<site>/core`` scopes (PR 29).

A site is the controller's (``stored``) where the edit injects into it
(``self_max_pixels``) or the store keeps its maps (``store``: up to half the
latent's side squared, as ``models/config.py:unet_layout`` scales the
paper's 32 x 32): its probabilities are materialized. Every other site goes
to ``nn.fused_attention``, which runs it on the flash kernel where the
program's own table has a geometry for the shape (``kernel``) and on XLA's
einsum chain where it has none (``einsum``: in neither metric). Site names
are built from the layout (place and index in call order), as the program
builds its scopes; nothing is read off a name.
"""

from __future__ import annotations

import re

from . import flops, scopes

_CORE = re.compile(r"/self_attn/([a-z]+\d+)/core(?:/|$)")


def classes(config: dict, traffic: dict):
    """``{site name: "kernel" | "stored" | "einsum"}`` over the U-Net's self
    sites; None where the program has no ``nn.flash_block`` to ask."""
    try:
        from p2p_tpu.models import nn
    except ImportError:
        return None
    if not hasattr(nn, "flash_block"):
        return None
    uc, edit = config["unet"], traffic["edit"]
    bound = edit["self_max_pixels"]
    if edit.get("store"):
        bound = max(bound, (uc["sample_size"] // 2) ** 2)
    out, index = {}, 0
    for place, _, pixels, channels in flops.unet_sites(uc):
        head = uc["attention_head_size"] or channels // uc["num_attention_heads"]
        for _ in range(uc["transformer_depth"]):
            if pixels <= bound:
                out[f"{place}{index}"] = "stored"
            elif nn.flash_block(pixels, head, 4) is not None:
                out[f"{place}{index}"] = "kernel"
            else:
                out[f"{place}{index}"] = "einsum"
            index += 2                      # self, then cross, in call order
    return out


def core_ms_per_step(run, which: str):
    """ms a step of the loop's device time under ``self_attn/<site>/core``
    of the sites of class ``which``; None without a scoped trace."""
    scoped = scopes.load(run)
    if (not scoped or not scoped.steps
            or scoped.scoped_pct < scopes.SCOPED_FLOOR_PCT):
        return None
    sites = classes(run.config, run.traffic)
    if sites is None:
        return None
    ns = 0.0
    for r in scoped.rows:
        m = _CORE.search(r.scope) if r.op.loop else None
        if m and sites.get(m.group(1)) == which:
            ns += r.op.dur
    return ns / scoped.ndev / scoped.steps / 1e6
