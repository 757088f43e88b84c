"""Reusable jaxpr walkers — the contract pass's vocabulary.

Generalizes the ad-hoc walker ``tests/test_phase_cache.py`` grew for the
phase-2 "no 2B tensors" proof into the shared helpers every contract (and
that test) now uses: flatten a jaxpr recursively, pull shapes, find scans,
find callbacks, find dtype conversions. Everything here operates on
``jax.core`` data structures only — no tracing, no compilation.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple


def all_eqns(jaxpr) -> list:
    """Every equation in ``jaxpr``, recursing into sub-jaxprs (scan / cond /
    pjit / while bodies), so nothing hides one nesting level down. Accepts
    a ``ClosedJaxpr`` or a raw ``Jaxpr``."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    eqns = []
    for eqn in jaxpr.eqns:
        eqns.append(eqn)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                eqns.extend(all_eqns(sub))
    return eqns


def _sub_jaxprs(param) -> Iterable:
    """Jaxprs embedded in one eqn param: a ClosedJaxpr or raw Jaxpr
    (``remat2`` carries one), or a list/tuple of them (cond/switch carry
    `branches`)."""
    def is_jaxpr(x):
        return hasattr(x, "jaxpr") or hasattr(x, "eqns")

    if is_jaxpr(param):
        yield param
    elif isinstance(param, (list, tuple)):
        for item in param:
            if is_jaxpr(item):
                yield item


def eqn_shapes(eqns) -> List[Tuple[int, ...]]:
    """Shapes of every in/out var across ``eqns`` (duplicates preserved —
    footprint questions care about how often a shape appears)."""
    out = []
    for eqn in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
    return out


def top_level_scans(jaxpr) -> list:
    """The outermost ``scan`` eqns of ``jaxpr`` in program order, looking
    through a single wrapping ``jit``/``custom_*`` level (tracing a jitted
    entry point wraps the whole body in one jit eqn)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    if scans:
        return scans
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "custom_vjp_call",
                                  "custom_jvp_call", "remat2"):
            # jit/remat2 keep the body under 'jaxpr', the custom_* calls
            # under 'call_jaxpr'.
            for key in ("jaxpr", "call_jaxpr"):
                for sub in _sub_jaxprs(eqn.params.get(key)):
                    inner = top_level_scans(sub)
                    if inner:
                        return inner
    return scans


def scan_body(scan_eqn) -> list:
    """All eqns (recursive) of one scan eqn's body."""
    return all_eqns(scan_eqn.params["jaxpr"])


def callback_eqns(eqns) -> list:
    """Host-callback equations: ``debug_callback`` (the progress/obs sink
    channel), ``io_callback``, ``pure_callback`` — anything that escapes to
    the host mid-program."""
    return [e for e in eqns if "callback" in e.primitive.name]


def f64_eqns(eqns) -> list:
    """Equations producing (or converting to) float64 — the dtype-promotion
    contract. Catches both explicit ``convert_element_type`` to f64 and any
    op whose output aval is f64 (a promotion that skipped an explicit
    convert)."""
    import numpy as np

    bad = []
    for eqn in eqns:
        if eqn.primitive.name == "convert_element_type" and \
                np.dtype(eqn.params.get("new_dtype")) == np.float64:
            bad.append(eqn)
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64:
                bad.append(eqn)
                break
    return bad


def doubled_batch_shapes(shapes: Sequence[Tuple[int, ...]], group_batch: int,
                         max_tokens: Optional[int] = None,
                         lead_dims: Tuple[int, ...] = ()) -> list:
    """Shapes carrying the CFG-doubled batch ``2B`` — the phase-2 footprint
    detector (from tests/test_phase_cache.py, generalized).

    A hit is a ≥3-D tensor whose batch axis equals ``2 * group_batch``:
    4-D feature maps ``(2B, h, w, c)`` or 3-D token-major tensors
    ``(2B, P, C)`` with ``P ≤ max_tokens`` (so tiny coincidental dims don't
    count). ``lead_dims`` prefixes the expected batch position — a vmapped
    serve program carries a leading group axis, so its doubled tensors look
    like ``(G, 2B, ...)``: pass ``lead_dims=(G,)``.
    """
    two_b = 2 * group_batch
    k = len(lead_dims)
    hits = []
    for s in shapes:
        if len(s) < 3 + k or tuple(s[:k]) != tuple(lead_dims):
            continue
        body = s[k:]
        if body[0] != two_b:
            continue
        if len(body) == 4 or (
                len(body) == 3 and (max_tokens is None
                                    or body[1] <= max_tokens)):
            hits.append(s)
    return hits


def folded_batch_shapes(shapes: Sequence[Tuple[int, ...]],
                        batch: int) -> list:
    """4-D feature maps whose leading dim equals ``batch`` — the form a
    vmapped program's activations take after vmap folds the mapped group
    axis into the conv batch axis: a serve bucket's phase-1 CFG tensors are
    ``(G·2B, h, w, c)``. Only 4-D counts: weight tensors (conv kernels are
    ``(kh, kw, cin, cout)``, projections ≤ 3-D) can't collide."""
    return [s for s in shapes if len(s) == 4 and s[0] == batch]
