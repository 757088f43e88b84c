"""Checkpoint-readiness reports (`p2p_tpu.models.checkpoint_check`, surfaced
as `p2p-tpu check` and `tools/check_checkpoint.py`) against synthetic
diffusers-layout directories: a correct dir reports READY;
shape drift, missing/unmapped tensors, scheduler-config drift, and missing
tokenizer files each surface as a named problem instead of a load-time crash.
"""

import json
import os
import shutil

import numpy as np
import pytest
import torch

import jax

from p2p_tpu.models import TINY, init_text_encoder, init_unet
from p2p_tpu.models import vae as vae_mod
from p2p_tpu.models import checkpoint_check as cc
from p2p_tpu.models.checkpoint import (export_state_dict,
                                       text_encoder_entries, unet_entries,
                                       vae_entries)


def _write_bin(sd, dirpath, filename):
    os.makedirs(dirpath, exist_ok=True)
    # np.array: one writable C-contiguous copy — jax exports arrive as
    # non-writable views and torch.from_numpy warns on those (the suite's
    # one warning otherwise).
    torch.save({k: torch.from_numpy(np.array(v))
                for k, v in sd.items()}, os.path.join(dirpath, filename))


def _write_scheduler(root, **overrides):
    os.makedirs(os.path.join(root, "scheduler"), exist_ok=True)
    sc = TINY.scheduler
    cfg = dict(num_train_timesteps=sc.num_train_timesteps,
               beta_start=sc.beta_start, beta_end=sc.beta_end,
               beta_schedule=sc.beta_schedule,
               prediction_type=sc.prediction_type,
               clip_sample=sc.clip_sample,
               set_alpha_to_one=sc.set_alpha_to_one,
               steps_offset=sc.ddim_steps_offset)
    cfg.update(overrides)
    with open(os.path.join(root, "scheduler", "scheduler_config.json"), "w") as f:
        json.dump(cfg, f)


@pytest.fixture(scope="module")
def good_dir(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ckpt_ready"))
    cfg = TINY
    _write_bin(export_state_dict(init_unet(jax.random.PRNGKey(0), cfg.unet),
                                 unet_entries(cfg.unet)),
               os.path.join(root, "unet"), "diffusion_pytorch_model.bin")
    _write_bin(export_state_dict(
        init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        text_encoder_entries(cfg.text)),
        os.path.join(root, "text_encoder"), "pytorch_model.bin")
    _write_bin(export_state_dict(vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
                                 vae_entries(cfg.vae)),
               os.path.join(root, "vae"), "diffusion_pytorch_model.bin")
    _write_scheduler(root)
    tok = os.path.join(root, "tokenizer")
    os.makedirs(tok, exist_ok=True)
    with open(os.path.join(tok, "vocab.json"), "w") as f:
        json.dump({}, f)
    with open(os.path.join(tok, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n")
    return root


def test_ready_dir_reports_ready(good_dir):
    rep = cc.check_checkpoint(good_dir, "sd14", config=TINY)
    assert rep.ok, vars(rep)
    for s in rep.submodels:
        assert s.ok and s.n_mapped > 0 and not s.unmapped
    assert rep.scheduler_diffs == [] and rep.scheduler_error is None


def test_cli_exit_codes(good_dir, tmp_path, monkeypatch, capsys):
    # The CLI path resolves real presets; exercise main() via a tiny-config
    # monkeypatch so no SD-scale eval_shape is needed.
    monkeypatch.setitem(cc.__dict__, "check_checkpoint",
                        lambda d, p, config=None: cc.Report(preset=p))
    assert cc.main([str(tmp_path), "--preset", "sd14"]) == 0
    assert "READY" in capsys.readouterr().out


def test_p2p_tpu_cli_check_subcommand(good_dir, monkeypatch, capsys):
    from p2p_tpu import cli

    monkeypatch.setitem(cc.__dict__, "check_checkpoint",
                        lambda d, p, config=None: cc.Report(preset=p))
    assert cli.main(["check", good_dir, "--preset", "sd14"]) == 0
    assert "READY" in capsys.readouterr().out


def test_detects_shape_and_key_drift(good_dir, tmp_path):
    root = str(tmp_path / "drift")
    shutil.copytree(good_dir, root)
    p = os.path.join(root, "unet", "diffusion_pytorch_model.bin")
    sd = torch.load(p, weights_only=True)
    # Wrong shape on one tensor, one mapped tensor dropped, one stray added.
    sd["conv_in.weight"] = torch.zeros(1, 2, 3, 3)
    del sd["conv_out.bias"]
    sd["totally_new.weight"] = torch.zeros(4)
    torch.save(sd, p)
    rep = cc.check_checkpoint(root, "sd14", config=TINY)
    unet = rep.submodels[0]
    assert not rep.ok and not unet.ok
    assert any("conv_in.weight" in m for m in unet.shape_mismatches)
    assert "conv_out.bias" in unet.missing
    assert "totally_new.weight" in unet.unmapped
    # The untouched sub-models still pass.
    assert rep.submodels[1].ok and rep.submodels[2].ok


def test_detects_scheduler_drift(good_dir, tmp_path):
    root = str(tmp_path / "sched")
    shutil.copytree(good_dir, root)
    _write_scheduler(root, beta_end=0.02, prediction_type="v_prediction")
    rep = cc.check_checkpoint(root, "sd14", config=TINY)
    assert not rep.ok
    joined = " ".join(rep.scheduler_diffs)
    assert "beta_end" in joined and "prediction_type" in joined


def test_missing_weights_and_tokenizer(tmp_path):
    rep = cc.check_checkpoint(str(tmp_path), "sd14", config=TINY)
    assert not rep.ok
    assert all(s.error for s in rep.submodels)
    assert rep.tokenizer_error is not None
    assert rep.scheduler_error is not None  # warning, not a blocker by itself


def test_safetensors_header_shapes(tmp_path):
    from safetensors.numpy import save_file

    path = str(tmp_path / "w.safetensors")
    arrs = {"x.weight": np.zeros((5, 7), np.float32),
            "y.bias": np.ones((3,), np.float32)}
    save_file(arrs, path)
    assert cc.read_shapes(path) == {"x.weight": (5, 7), "y.bias": (3,)}


def test_shape_transforms():
    assert cc._shape_fwd("linear", (8, 4)) == (4, 8)
    assert cc._shape_fwd("conv", (16, 8, 3, 3)) == (3, 3, 8, 16)
    assert cc._shape_fwd("none", (9,)) == (9,)


def test_ldm_layout_bert_vqvae_dirs(tmp_path):
    # The CompVis LDM-256 repo names its sub-models bert/ and vqvae/; both
    # the readiness check and load_pipeline must resolve that layout.
    from p2p_tpu.models import TINY_LDM
    from p2p_tpu.models.checkpoint import (ldm_text_encoder_entries,
                                           load_pipeline)

    root = str(tmp_path / "ldm")
    cfg = TINY_LDM
    _write_bin(export_state_dict(init_unet(jax.random.PRNGKey(0), cfg.unet),
                                 unet_entries(cfg.unet)),
               os.path.join(root, "unet"), "diffusion_pytorch_model.bin")
    _write_bin(export_state_dict(
        init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        ldm_text_encoder_entries(cfg.text)),
        os.path.join(root, "bert"), "pytorch_model.bin")
    _write_bin(export_state_dict(vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
                                 vae_entries(cfg.vae)),
               os.path.join(root, "vqvae"), "diffusion_pytorch_model.bin")
    tok = os.path.join(root, "tokenizer")
    os.makedirs(tok, exist_ok=True)
    with open(os.path.join(tok, "vocab.txt"), "w") as f:
        f.write("\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "a", "cat",
                           "##s"]) + "\n")

    rep = cc.check_checkpoint(root, "ldm256", config=cfg)
    for s in rep.submodels:
        assert s.error is None and not s.missing and not s.shape_mismatches, vars(s)
    assert rep.tokenizer_error is None
    assert rep.scheduler_error is not None  # no scheduler json → warning only

    pipe = load_pipeline(root, cfg)
    assert pipe.tokenizer.model_max_length == cfg.text.max_length


@pytest.mark.parametrize("preset", ["sd14", "sd21", "sd21base", "ldm256"])
def test_fullscale_preset_tables_consistent(preset):
    # Every real preset's mapping tables must agree with its init tree at
    # FULL scale: each mapped path exists with a defined shape (eval_shape —
    # no allocation). This is the U-Net/VAE analogue of the full-scale text
    # validation in test_text_encoder_fullscale.py: a drifted entry table or
    # config (wrong level count, head_dim, channel_mults) fails here, not on
    # first real-weights contact.
    from p2p_tpu.models import config as cfg_mod
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.models.checkpoint import (ldm_text_encoder_entries,
                                           text_encoder_entries, unet_entries,
                                           vae_entries)
    from p2p_tpu.models.text_encoder import init_text_encoder
    from p2p_tpu.models.unet import init_unet

    cfg = {"sd14": cfg_mod.SD14, "sd21": cfg_mod.SD21,
           "sd21base": cfg_mod.SD21_BASE, "ldm256": cfg_mod.LDM256}[preset]
    text_entries = (ldm_text_encoder_entries(cfg.text)
                    if cfg.text.arch == "ldmbert"
                    else text_encoder_entries(cfg.text))
    for entries, init_fn, floor in (
            (unet_entries(cfg.unet), lambda k: init_unet(k, cfg.unet), 400),
            (text_entries,
             lambda k: init_text_encoder(k, cfg.text), 100),
            (vae_entries(cfg.vae),
             lambda k: vae_mod.init_vae(k, cfg.vae), 100)):
        shapes = cc._expected_shapes(entries, init_fn)
        assert len(shapes) >= floor
        assert all(len(s) > 0 for _, s in shapes.values())
        # their-names must be unique — duplicate targets would silently
        # overwrite on export.
        assert len(shapes) == len(entries)
