"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, number by number, each with its limit."""

from __future__ import annotations

import numpy as np


def image_rel_err(served: np.ndarray, reference: np.ndarray) -> float:
    """Mean absolute difference of two uint8 images over the reference's
    standard deviation: relative, because random weights set the contrast."""
    a = served.astype(np.float64)
    b = reference.astype(np.float64)
    spread = b.std()
    if not np.isfinite(spread) or spread == 0.0:
        return float("inf")
    return float(np.abs(a - b).mean() / spread)


def rel_rms(served: np.ndarray, reference: np.ndarray) -> float:
    """Root mean square of the difference over that of the reference."""
    a = np.asarray(served, np.float64)
    b = np.asarray(reference, np.float64)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def sample_indices(n: int, k: int, seed: int):
    """``k`` of ``range(n)`` drawn from the seed (all of them if fewer)."""
    import random

    return sorted(random.Random(seed ^ 0x5EED).sample(range(n), min(k, n)))


def check_groups(run, weights, items) -> dict:
    """Run the configuration's plain reference over edit groups that the
    window served and compare. ``items``: dicts with ``kind``, ``prompts``
    (source, target), ``key`` and ``noise_shape`` / ``noise_pick`` (how the
    request's noise was drawn), ``images`` (2, H, W, 3) uint8, optionally
    ``latents``, and optionally ``edit`` (what this kind overrides in the
    mix's edit, such as its gate). Returns ``{number: {"value", "limit"}}``, the worst over the
    groups."""
    import jax.numpy as jnp

    from .harness import load_module

    ref = load_module("reference", run.config["reference"])
    limits = run.traffic["check"]["limits"]
    fns, worst = {}, {name: 0.0 for name in limits}
    for item in items:
        edit = dict(run.traffic["edit"], kind=item["kind"], **item.get("edit", {}))
        if item["kind"] not in fns:
            fns[item["kind"]] = ref.make_edit_fn(run.config, edit)
        x_T = ref.noise(item["key"], item["noise_shape"])[item["noise_pick"]]
        align = {k: jnp.asarray(v)
                 for k, v in ref.alignment(run.config, edit, item["prompts"]).items()}
        img, lat = fns[item["kind"]](
            weights, x_T, jnp.asarray(ref.prompt_ids(run.config, item["prompts"])),
            align)
        img8 = np.asarray(ref.to_uint8(img))
        readings = {"image_rel_err": max(
            image_rel_err(item["images"][j], img8[j]) for j in range(len(img8)))}
        if "latents" in item:
            readings["latent_rel_err"] = rel_rms(item["latents"], np.asarray(lat))
        for name in limits:
            worst[name] = max(worst[name], readings[name])
    return {name: {"value": worst[name], "limit": limits[name]} for name in limits}
