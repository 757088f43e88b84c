"""Native pipeline snapshots: orbax param trees + a JSON config manifest.

The diffusers-format loader (`checkpoint.load_pipeline`) converts torch
tensor names/layouts on every process start; a native snapshot saves the
*converted* JAX pytrees once and restores them directly — the idiomatic
TPU checkpoint path (orbax is JAX's checkpointing library, sharding-aware
on restore). The reference has no equivalent: its weights always come from
`StableDiffusionPipeline.from_pretrained` (`/root/reference/main.py:29`).

Layout on disk::

    <dir>/config.json        dataclasses.asdict(PipelineConfig) + format tag
    <dir>/params/            orbax PyTreeCheckpointer tree
                             {"unet": ..., "text": ..., "vae": ...}

The tokenizer is deliberately NOT serialized — it is host-side code, not
arrays; pass the same tokenizer (HF-backed or hash) to
:func:`load_pipeline_native` that the snapshot was built with.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

from .config import (
    PipelineConfig,
    SchedulerConfig,
    TextEncoderConfig,
    UNetConfig,
    VAEConfig,
)

_FORMAT = 1


def _tuplify(d: dict) -> dict:
    """JSON round-trip turns tuples into lists; the frozen configs want
    tuples back (they're hashed as static jit arguments)."""
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def config_to_dict(cfg: PipelineConfig) -> dict:
    out = dataclasses.asdict(cfg)
    out["_format"] = _FORMAT
    return out


def config_from_dict(d: dict) -> PipelineConfig:
    fmt = d.get("_format", _FORMAT)
    if fmt != _FORMAT:
        raise ValueError(f"unsupported native-snapshot format {fmt} "
                         f"(this build reads format {_FORMAT})")
    return PipelineConfig(
        name=d["name"],
        unet=UNetConfig(**_tuplify(d["unet"])),
        text=TextEncoderConfig(**_tuplify(d["text"])),
        vae=VAEConfig(**_tuplify(d["vae"])),
        image_size=d["image_size"],
        guidance_scale=d["guidance_scale"],
        num_steps=d["num_steps"],
        scheduler=SchedulerConfig(**_tuplify(d["scheduler"])),
    )


def save_pipeline_native(pipe, path: str, overwrite: bool = False) -> None:
    """Snapshot a bound pipeline's params + config under ``path``.

    Refuses an existing snapshot unless ``overwrite=True`` (which removes
    it first); the manifest is written only after the params commit, so a
    failed save can never leave a fresh config.json over stale params."""
    import shutil

    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    params_dir = os.path.join(path, "params")
    if os.path.exists(params_dir):
        if not overwrite:
            raise FileExistsError(
                f"native snapshot already exists at {path}; "
                f"pass overwrite=True to replace it")
        shutil.rmtree(path)
    os.makedirs(path, exist_ok=True)
    ocp.PyTreeCheckpointer().save(
        params_dir,
        {"unet": pipe.unet_params, "text": pipe.text_params,
         "vae": pipe.vae_params})
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config_to_dict(pipe.config), f, indent=1)


def load_pipeline_native(path: str, tokenizer,
                         config: Optional[PipelineConfig] = None,
                         shard=None):
    """Restore a pipeline saved by :func:`save_pipeline_native`.

    The params restore to HOST numpy arrays regardless of the topology the
    snapshot was saved on (replaying a saved device sharding on a different
    topology is unsafe — orbax's own warning), so placement is explicit:
    pass ``shard``, a callable over the ``{"unet","text","vae"}`` tree
    (e.g. ``lambda t: {**t, "unet": shard_params(t["unet"], mesh)}``), or
    let jit move the host arrays on first use. ``config`` overrides the
    stored manifest."""
    import numpy as np

    import jax
    import orbax.checkpoint as ocp

    from ..engine.sampler import Pipeline

    path = os.path.abspath(path)
    if config is None:
        with open(os.path.join(path, "config.json")) as f:
            config = config_from_dict(json.load(f))
    ckptr = ocp.PyTreeCheckpointer()
    params_dir = os.path.join(path, "params")
    meta = ckptr.metadata(params_dir).item_metadata.tree
    restore_args = jax.tree.map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), meta)
    params = ckptr.restore(params_dir, restore_args=restore_args)
    if shard is not None:
        params = shard(params)
    return Pipeline(config=config, unet_params=params["unet"],
                    text_params=params["text"], vae_params=params["vae"],
                    tokenizer=tokenizer)
