"""Checkpoint-readiness report: verify a diffusers checkpoint dir against a
preset WITHOUT loading it into a model (CLI: `p2p-tpu check`, or
`python tools/check_checkpoint.py`).

First contact with real weights should be a config report, not a crash.
For each sub-model the tool diffs the checkpoint's
tensor names/shapes against the mapping tables in
`p2p_tpu/models/checkpoint.py` (both directions: mapped-but-missing and
present-but-unmapped), using `jax.eval_shape` over the init functions so the
expected tree costs no memory, and safetensors *header* parsing so multi-GB
weight files cost no I/O. It also diffs `scheduler_config.json` against the
preset's `SchedulerConfig` and checks the tokenizer files.

    python tools/check_checkpoint.py /path/to/sd14-checkpoint --preset sd14

The reference's ground truth for these directories is
`StableDiffusionPipeline.from_pretrained` (`/root/reference/main.py:29`,
`/root/reference/null_text.py:28-31`) and
`DiffusionPipeline.from_pretrained("CompVis/ldm-text2im-large-256")`
(`/root/reference/prompt-to-prompt_ldm.ipynb` per SURVEY §2.9).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import struct
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# Shape-level reading + transforms (no tensor data movement)
# ---------------------------------------------------------------------------


def read_shapes(path: str) -> Dict[str, Tuple[int, ...]]:
    """{tensor_name: shape} for a weights file.

    ``.safetensors``: parsed straight from the 8-byte-length-prefixed JSON
    header — no tensor bytes are read. torch ``.bin``/``.pt``: falls back to a
    full ``torch.load`` (the pickle stream interleaves metadata and storage).
    """
    if path.endswith(".safetensors"):
        with open(path, "rb") as f:
            (hlen,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(hlen))
        return {k: tuple(v["shape"]) for k, v in header.items()
                if k != "__metadata__"}
    import torch

    sd = torch.load(path, map_location="meta", weights_only=True)
    return {k: tuple(v.shape) for k, v in sd.items()}


def _shape_fwd(kind: str, shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Checkpoint-side shape → our-side shape, per the layout transform."""
    if kind == "linear":
        return tuple(reversed(shape))
    if kind == "conv":
        o, i, kh, kw = shape
        return (kh, kw, i, o)
    return tuple(shape)


# ---------------------------------------------------------------------------
# Report structure
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SubReport:
    name: str
    weights_file: Optional[str] = None
    n_mapped: int = 0
    missing: List[str] = dataclasses.field(default_factory=list)
    unmapped: List[str] = dataclasses.field(default_factory=list)
    shape_mismatches: List[str] = dataclasses.field(default_factory=list)
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.error is None and not self.missing
                and not self.shape_mismatches and not self.unmapped)


@dataclasses.dataclass
class Report:
    preset: str
    submodels: List[SubReport] = dataclasses.field(default_factory=list)
    scheduler_diffs: List[str] = dataclasses.field(default_factory=list)
    scheduler_error: Optional[str] = None
    tokenizer_error: Optional[str] = None

    @property
    def ok(self) -> bool:
        # Scheduler diffs are genuine blockers (wrong betas → wrong images);
        # a missing scheduler_config.json is only a warning (our preset's
        # defaults apply), matching load_pipeline's behavior.
        return (all(s.ok for s in self.submodels)
                and not self.scheduler_diffs
                and self.tokenizer_error is None)


# ---------------------------------------------------------------------------
# Per-sub-model check
# ---------------------------------------------------------------------------

# Diffusers checkpoint-dir layouts: SD repos use unet/text_encoder/vae;
# the CompVis LDM repo names them unet/bert/vqvae.
_SUBDIRS = {
    "unet": ("unet",),
    "text_encoder": ("text_encoder", "bert"),
    "vae": ("vae", "vqvae"),
}
_WEIGHT_NAMES = {
    "unet": ("diffusion_pytorch_model.safetensors", "diffusion_pytorch_model.bin"),
    "text_encoder": ("model.safetensors", "pytorch_model.bin"),
    "vae": ("diffusion_pytorch_model.safetensors", "diffusion_pytorch_model.bin"),
}


def _expected_shapes(entries, init_fn) -> Dict[str, Tuple[str, Tuple[int, ...]]]:
    """{their_name: (kind, our_shape)} via eval_shape — zero allocation."""
    import jax

    from .checkpoint import _get

    tree = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
    out = {}
    for our_path, their_name, kind in entries:
        leaf = _get(tree, our_path)
        out[their_name] = (kind, tuple(leaf.shape))
    return out


def _check_submodel(name: str, dirpath: str, entries, init_fn) -> SubReport:
    from .checkpoint import _find_weights_file

    rep = SubReport(name=name)
    sub = next((os.path.join(dirpath, d) for d in _SUBDIRS[name]
                if os.path.isdir(os.path.join(dirpath, d))), None)
    if sub is None:
        rep.error = f"no {'/'.join(_SUBDIRS[name])} directory in {dirpath}"
        return rep
    try:
        rep.weights_file = _find_weights_file(sub, _WEIGHT_NAMES[name])
    except FileNotFoundError as e:
        rep.error = str(e)
        return rep

    got = read_shapes(rep.weights_file)
    want = _expected_shapes(entries, init_fn)
    rep.n_mapped = len(want)

    for their_name, (kind, our_shape) in want.items():
        if their_name not in got:
            rep.missing.append(their_name)
        elif _shape_fwd(kind, got[their_name]) != our_shape:
            rep.shape_mismatches.append(
                f"{their_name}: checkpoint {got[their_name]} "
                f"-> {_shape_fwd(kind, got[their_name])} vs ours {our_shape}")
    # Same ignore set as apply_state_dict's strict mode.
    rep.unmapped = [k for k in got if k not in want
                    and not k.endswith("position_ids")
                    and not k.startswith("to_logits")]
    return rep


# ---------------------------------------------------------------------------
# Scheduler + tokenizer checks
# ---------------------------------------------------------------------------

# diffusers scheduler_config.json field → our SchedulerConfig attribute.
_SCHED_FIELDS = (
    ("num_train_timesteps", "num_train_timesteps"),
    ("beta_start", "beta_start"),
    ("beta_end", "beta_end"),
    ("beta_schedule", "beta_schedule"),
    ("prediction_type", "prediction_type"),
    ("clip_sample", "clip_sample"),
    ("set_alpha_to_one", "set_alpha_to_one"),
)


def _check_scheduler(dirpath: str, sched) -> Tuple[List[str], Optional[str]]:
    path = os.path.join(dirpath, "scheduler", "scheduler_config.json")
    if not os.path.exists(path):
        return [], f"no {path} — preset scheduler defaults will apply"
    with open(path) as f:
        theirs = json.load(f)
    diffs = []
    for their_key, our_key in _SCHED_FIELDS:
        if their_key not in theirs:
            continue  # older configs omit e.g. prediction_type → default ok
        tv, ov = theirs[their_key], getattr(sched, our_key)
        same = (np.isclose(tv, ov) if isinstance(ov, float) else tv == ov)
        if not same:
            diffs.append(f"{their_key}: checkpoint {tv!r} vs preset {ov!r}")
    # steps_offset lives on the pipeline's one scheduler; ours is per-kind.
    if "steps_offset" in theirs:
        off = theirs["steps_offset"]
        if off not in (sched.plms_steps_offset, sched.ddim_steps_offset):
            diffs.append(f"steps_offset: checkpoint {off!r} vs preset "
                         f"plms={sched.plms_steps_offset} "
                         f"ddim={sched.ddim_steps_offset}")
    return diffs, None


def _check_tokenizer(dirpath: str, arch: str) -> Optional[str]:
    tok = os.path.join(dirpath, "tokenizer")
    if not os.path.isdir(tok):
        return f"no tokenizer/ directory in {dirpath}"
    need = (("vocab.txt",) if arch == "ldmbert"
            else ("vocab.json", "merges.txt"))
    missing = [n for n in need if not os.path.exists(os.path.join(tok, n))]
    if missing:
        return f"tokenizer/ missing {missing} (need {need} for {arch})"
    return None


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _real_presets():
    # tiny* presets exist for tests (synthetic checkpoints pass config=);
    # the readiness report targets real released directories.
    from .config import PRESET_CONFIGS

    return tuple(k for k in PRESET_CONFIGS if not k.startswith("tiny"))


PRESETS = _real_presets()


def check_checkpoint(dirpath: str, preset: str, config=None) -> Report:
    """``config`` overrides the preset's PipelineConfig (tests use tiny
    configs against synthetic checkpoint dirs)."""
    from . import vae as vae_mod
    from .checkpoint import (ldm_text_encoder_entries, one_tower,
                             text_encoder_entries, unet_entries, vae_entries)
    from .config import PRESET_CONFIGS
    from .denoiser import require_unet
    from .text_encoder import init_text_encoder
    from .unet import init_unet

    cfg = config if config is not None else PRESET_CONFIGS[preset]
    require_unet(cfg.unet, "the checkpoint key map")
    text = one_tower(cfg)
    text_entries = (ldm_text_encoder_entries(text) if text.arch == "ldmbert"
                    else text_encoder_entries(text))

    rep = Report(preset=preset)
    rep.submodels = [
        _check_submodel("unet", dirpath, unet_entries(cfg.unet),
                        lambda k: init_unet(k, cfg.unet)),
        _check_submodel("text_encoder", dirpath, text_entries,
                        lambda k: init_text_encoder(k, text)),
        _check_submodel("vae", dirpath, vae_entries(cfg.vae),
                        lambda k: vae_mod.init_vae(k, cfg.vae)),
    ]
    rep.scheduler_diffs, rep.scheduler_error = _check_scheduler(
        dirpath, cfg.scheduler)
    rep.tokenizer_error = _check_tokenizer(dirpath, text.arch)
    return rep


def _print_report(rep: Report) -> None:
    def _head(items, n=5):
        return "".join(f"\n      {x}" for x in items[:n]) + (
            f"\n      ... +{len(items) - n} more" if len(items) > n else "")

    print(f"checkpoint-readiness report (preset {rep.preset})")
    for s in rep.submodels:
        mark = "OK " if s.ok else "FAIL"
        print(f"  [{mark}] {s.name}: "
              + (s.error or f"{s.n_mapped} mapped tensors "
                 f"({os.path.basename(s.weights_file)})"))
        if s.missing:
            print(f"    missing from checkpoint ({len(s.missing)}):"
                  + _head(s.missing))
        if s.shape_mismatches:
            print(f"    shape mismatches ({len(s.shape_mismatches)}):"
                  + _head(s.shape_mismatches))
        if s.unmapped:
            print(f"    unmapped checkpoint tensors ({len(s.unmapped)}):"
                  + _head(s.unmapped))
    if rep.scheduler_error:
        print(f"  [warn] scheduler: {rep.scheduler_error}")
    elif rep.scheduler_diffs:
        print(f"  [FAIL] scheduler config differs:" + _head(rep.scheduler_diffs))
    else:
        print("  [OK ] scheduler config matches preset")
    if rep.tokenizer_error:
        print(f"  [FAIL] tokenizer: {rep.tokenizer_error}")
    else:
        print("  [OK ] tokenizer files present")
    print("READY" if rep.ok else "NOT READY")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("checkpoint_dir")
    ap.add_argument("--preset", choices=PRESETS, required=True)
    args = ap.parse_args(argv)
    rep = check_checkpoint(args.checkpoint_dir, args.preset)
    _print_report(rep)
    return 0 if rep.ok else 1


if __name__ == "__main__":
    sys.exit(main())
