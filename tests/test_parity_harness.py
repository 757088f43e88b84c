"""The real-weights parity harness (tools/parity_real_weights.py) exercised
end-to-end against an HF-format random-weight checkpoint — so the day a real
SD-1.4 directory is available, the golden-image comparison the north star
asks for (BASELINE.json:5, `/root/reference/main.py:29`) is a one-command,
already-rehearsed exercise."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from p2p_tpu.utils.cache import default_cache_dir

torch = pytest.importorskip("torch")

from p2p_tpu.engine.sampler import Pipeline
from p2p_tpu.models import TINY, init_text_encoder, init_unet
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models.checkpoint import (
    export_state_dict,
    text_encoder_entries,
    unet_entries,
    vae_entries,
)

from test_load_pipeline import _write_bin, _write_clip_vocab

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HARNESS = os.path.join(REPO, "tools", "parity_real_weights.py")


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # One resolver for the whole repo (p2p_tpu.utils.cache): a pre-set
    # JAX_COMPILATION_CACHE_DIR is respected (shared CI cache), else the
    # repo-local default the in-process conftest also uses.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   default_cache_dir())
    return env


@pytest.mark.slow
def test_harness_end_to_end_on_random_hf_checkpoint(tmp_path):
    ckpt = tmp_path / "ckpt"
    cfg = TINY
    _write_bin(export_state_dict(init_unet(jax.random.PRNGKey(20), cfg.unet),
                                 unet_entries(cfg.unet)),
               ckpt / "unet", "diffusion_pytorch_model.bin")
    _write_bin(export_state_dict(
        init_text_encoder(jax.random.PRNGKey(21), cfg.text),
        text_encoder_entries(cfg.text)),
        ckpt / "text_encoder", "pytorch_model.bin")
    _write_bin(export_state_dict(vae_mod.init_vae(jax.random.PRNGKey(22),
                                                  cfg.vae),
                                 vae_entries(cfg.vae)),
               ckpt / "vae", "diffusion_pytorch_model.bin")
    _write_clip_vocab(ckpt / "tokenizer")

    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, HARNESS, str(ckpt), "--preset", "tiny",
         "--steps", "2", "--dpm-operating-point", "--out-dir", str(out)],
        env=_cpu_env(), timeout=900, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, f"harness failed:\n{proc.stdout[-4000:]}"

    with open(out / "report.json") as f:
        report = json.load(f)
    assert report["pass"] is True
    stages = report["stages"]
    for name in ("text_encoder", "unet_eps", "loop_latent", "vae_decode",
                 "image"):
        assert name in stages, f"stage {name} missing from report"
    # Same weights on both sides: per-stage drift is float-reassociation
    # scale, and the images match to one uint8 level.
    assert stages["text_encoder"]["max_abs"] < 1e-3
    assert stages["image"]["max_abs"] <= 1
    assert (out / "ours_0.png").exists()
    assert (out / "torch_ref_0.png").exists()
    assert report["edit_precompute"]  # which precompute path was used
    # --dpm-operating-point: both solver renders + a PSNR in the report.
    assert (out / "quality_ddim4.png").exists()
    assert (out / "quality_dpm2.png").exists()
    assert report["dpm_operating_point"]["psnr_db"] > 0


@pytest.mark.slow
def test_real_sd14_checkpoint_parity_or_skip():
    """The actual real-weights run. Skips (visibly) in environments without
    the released SD-1.4 weights; with `P2P_REAL_SD14_DIR` set it is the
    golden-image comparison itself."""
    ckpt = os.environ.get("P2P_REAL_SD14_DIR", "")
    if not ckpt:
        pytest.skip("set P2P_REAL_SD14_DIR=/path/to/stable-diffusion-v1-4 "
                    "to run the real-weights parity check")
    proc = subprocess.run(
        [sys.executable, HARNESS, ckpt, "--preset", "sd14", "--steps", "3",
         "--out-dir", os.path.join(REPO, "parity_out")],
        env=_cpu_env(), timeout=7200, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, f"parity failed:\n{proc.stdout[-4000:]}"
