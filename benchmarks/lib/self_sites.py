"""The self-attention sites of a cell by how they ran, and the loop's device
time under each class's ``self_attn/<site>/core`` scopes (PR 29; PR 33).

A site's class is what the window's sampling program recorded of it when it
was traced (``Launch.self_sites`` of ``p2p_tpu/obs/launches.py``, through
``lib/launched.py``): ``edited`` where the controller injects into it or a
reader takes its map (the probabilities are materialized), ``kernel`` where
``nn.fused_attention`` sent it to the flash kernel, ``einsum`` where it took
XLA's chain, ``sharded`` over a mesh. Since PR 30 a stored map is kept only
for a reader, so in ``sd14.edit-replace`` and ``sd21.edit-replace`` the
launch says ``{'kernel': 10, 'edited': 6}``: the two largest levels' ten
sites are the kernel's, the controller has the six it injects into. Nothing
is worked out from the traffic's ``store`` or from ``nn.flash_block``: those
say what a table would answer, the launch says what ran. The configuration's
layout gives each site's name only (place and index in call order, as the
program builds its scopes); nothing is read off a name.
"""

from __future__ import annotations

import re
import sys

from . import flops, launched, scopes

_CORE = re.compile(r"/self_attn/([a-z]+\d+)/core(?:/|$)")


def classes(run, module: str):
    """``{site name: how}`` over the U-Net's self sites of ``module``'s
    program; None where the program recorded none (laid over an older
    parent), or other sites than the configuration's layout has."""
    hows = launched.self_site_hows(run, module)
    names = flops.self_site_names(flops.denoiser(run.config))
    if not hows or sorted(hows) != [2 * i for i in range(len(names))]:
        return None
    return {name: hows[2 * i] for i, name in enumerate(names)}


def core_ms_by_class(run):
    """``{how: ms a step}`` of the loop's device time under
    ``self_attn/<site>/core`` by the class of the site, made once and printed
    to stderr beside what is left of the part (the sites' ``qkv`` and
    ``out``); None without a scoped trace or without the launch's record."""
    if "_self_core_ms" not in run.__dict__:
        run._self_core_ms = _core_ms_by_class(run)
    return run._self_core_ms


def _core_ms_by_class(run):
    scoped = scopes.load(run)
    if (not scoped or not scoped.steps
            or scoped.scoped_pct < scopes.SCOPED_FLOOR_PCT):
        return None
    by_module, ns, rest = {}, {}, 0.0
    for r in scoped.rows:
        if not r.op.loop or r.part != "self_attn":
            continue
        m = _CORE.search(r.scope)
        if not m:
            rest += r.op.dur
            continue
        if r.op.module not in by_module:
            by_module[r.op.module] = classes(run, r.op.module)
        sites = by_module[r.op.module]
        if sites is None or m.group(1) not in sites:
            return None
        how = sites[m.group(1)]
        ns[how] = ns.get(how, 0.0) + r.op.dur
    per = scoped.ndev * scoped.steps * 1e6
    out = {how: v / per for how, v in ns.items()}
    print("self-attention core by how the site ran (ms/step): "
          + " ".join(f"{how}:{v:.6f}" for how, v in sorted(out.items()))
          + f"; the sites' qkv and out {rest / per:.6f}; together "
          f"{(sum(ns.values()) + rest) / per:.6f} of the part's "
          f"{scoped.loop_ms_per_step('self_attn'):.6f}", file=sys.stderr)
    return out


def core_ms_per_step(run, which: str):
    """ms a step under ``self_attn/<site>/core`` of the sites of class
    ``which``; None where nothing can be read, or no site is of that class."""
    return (core_ms_by_class(run) or {}).get(which)
