"""CLI smoke tests (tiny preset, random weights, CPU)."""

import os

import numpy as np
import pytest

from p2p_tpu.cli import main


def test_generate_writes_image(tmp_path):
    out = os.path.join(tmp_path, "img.png")
    assert main(["generate", "--quiet", "--prompt", "a cat", "--steps", "2",
                 "--out", out]) == 0
    assert os.path.exists(out)


def test_generate_seed_sweep_suffixes(tmp_path):
    out = os.path.join(tmp_path, "img.png")
    assert main(["generate", "--quiet", "--prompt", "a cat", "--steps", "2",
                 "--seeds", "1,2", "--out", out]) == 0
    assert os.path.exists(os.path.join(tmp_path, "img_00001.png"))
    assert os.path.exists(os.path.join(tmp_path, "img_00002.png"))


def test_edit_writes_pairs(tmp_path):
    out_dir = os.path.join(tmp_path, "run")
    assert main(["edit", "--quiet", "--source", "a cat riding a bike",
                 "--target", "a dog riding a bike", "--mode", "replace",
                 "--steps", "2", "--seeds", "7", "--out-dir", out_dir]) == 0
    assert os.path.exists(os.path.join(out_dir, "00007_y.jpg"))
    assert os.path.exists(os.path.join(out_dir, "00007_y_hat.jpg"))


def test_generate_batch_seeds_matches_sequential(tmp_path):
    from PIL import Image

    common = ["generate", "--quiet", "--prompt", "a cat riding a bike",
              "--steps", "2", "--seeds", "4,8"]
    seq = os.path.join(tmp_path, "s.png")
    bat = os.path.join(tmp_path, "b.png")
    assert main(common + ["--out", seq]) == 0
    assert main(common + ["--batch-seeds", "--out", bat]) == 0
    for seed in (4, 8):
        a = np.asarray(Image.open(
            os.path.join(tmp_path, f"s_{seed:05d}.png")), np.float32)
        b = np.asarray(Image.open(
            os.path.join(tmp_path, f"b_{seed:05d}.png")), np.float32)
        assert np.abs(a - b).mean() < 1.0, f"seed {seed} diverged"


def test_edit_batch_seeds_matches_sequential(tmp_path):
    """--batch-seeds runs the sweep engine (two programs total); its y/y_hat
    pairs must match the sequential per-seed loop on the same seeds (both
    draw the base latent as normal(PRNGKey(seed)))."""
    from PIL import Image

    seq_dir = os.path.join(tmp_path, "seq")
    bat_dir = os.path.join(tmp_path, "bat")
    common = ["edit", "--quiet", "--source", "a cat riding a bike",
              "--target", "a dog riding a bike", "--mode", "replace",
              "--steps", "2", "--seeds", "3,9"]
    assert main(common + ["--out-dir", seq_dir]) == 0
    assert main(common + ["--batch-seeds", "--out-dir", bat_dir]) == 0
    for seed in (3, 9):
        for kind in ("y", "y_hat"):
            a = np.asarray(Image.open(
                os.path.join(seq_dir, f"{seed:05d}_{kind}.jpg")), np.float32)
            b = np.asarray(Image.open(
                os.path.join(bat_dir, f"{seed:05d}_{kind}.jpg")), np.float32)
            # Same math modulo vmap reassociation and one JPEG round trip.
            assert np.abs(a - b).mean() < 3.0, f"seed {seed} {kind} diverged"


def test_edit_attn_maps_writes_heatmaps(tmp_path):
    out_dir = os.path.join(tmp_path, "run")
    maps_dir = os.path.join(tmp_path, "maps")
    assert main(["edit", "--quiet", "--source", "a cat riding a bike",
                 "--target", "a dog riding a bike", "--mode", "replace",
                 "--steps", "2", "--seeds", "5", "--out-dir", out_dir,
                 "--attn-maps", maps_dir]) == 0
    p = os.path.join(maps_dir, "00005_cross_attn.png")
    assert os.path.exists(p)
    from PIL import Image

    assert np.asarray(Image.open(p)).ndim == 3  # a real RGB heatmap grid
    # Incompatible with the batched path: rejected loudly, not ignored.
    with pytest.raises(SystemExit):
        main(["edit", "--quiet", "--source", "a", "--target", "b",
              "--mode", "replace", "--steps", "2", "--seeds", "1,2",
              "--batch-seeds", "--attn-maps", maps_dir,
              "--out-dir", out_dir])


def test_edit_self_attn_maps_writes_svd_grid(tmp_path):
    """--self-attn-maps: the reference's show_self_attention_comp
    (`/root/reference/main.py:330-350`) as a CLI artifact."""
    out_dir = os.path.join(tmp_path, "run")
    maps_dir = os.path.join(tmp_path, "selfmaps")
    assert main(["edit", "--quiet", "--source", "a cat riding a bike",
                 "--target", "a dog riding a bike", "--mode", "replace",
                 "--steps", "2", "--seeds", "5", "--out-dir", out_dir,
                 "--self-attn-maps", maps_dir]) == 0
    p = os.path.join(maps_dir, "00005_self_attn_svd.png")
    assert os.path.exists(p)
    from PIL import Image

    assert np.asarray(Image.open(p)).ndim == 3
    with pytest.raises(SystemExit):
        main(["edit", "--quiet", "--source", "a", "--target", "b",
              "--mode", "replace", "--steps", "2", "--seeds", "1,2",
              "--batch-seeds", "--self-attn-maps", maps_dir,
              "--out-dir", out_dir])


def test_invert_then_replay(tmp_path):
    from PIL import Image

    img_path = os.path.join(tmp_path, "in.png")
    rng = np.random.default_rng(0)
    Image.fromarray(rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)).save(img_path)
    art = os.path.join(tmp_path, "art.npz")
    assert main(["invert", "--quiet", "--image", img_path, "--prompt", "a cat",
                 "--steps", "2", "--inner-steps", "2", "--artifact", art]) == 0
    assert os.path.exists(art)
    out_dir = os.path.join(tmp_path, "replay")
    assert main(["replay", "--quiet", "--artifact", art, "--target", "a dog",
                 "--mode", "replace", "--out-dir", out_dir]) == 0
    assert os.path.exists(os.path.join(out_dir, "reconstruction.png"))
    assert os.path.exists(os.path.join(out_dir, "edited.png"))

    # --batch-targets: a multi-target edit sweep of the same artifact rides
    # the dp sweep engine (one program, per-step null embeddings broadcast
    # over groups) and matches the sequential replay per target.
    bat_dir = os.path.join(tmp_path, "replay_batch")
    assert main(["replay", "--quiet", "--artifact", art, "--target", "a dog",
                 "--target", "a fox", "--mode", "replace",
                 "--batch-targets", "--out-dir", bat_dir]) == 0
    assert os.path.exists(os.path.join(bat_dir, "reconstruction.png"))
    assert os.path.exists(os.path.join(bat_dir, "edited_01.png"))
    seq = np.asarray(Image.open(os.path.join(out_dir, "edited.png")), np.int32)
    bat = np.asarray(Image.open(os.path.join(bat_dir, "edited_00.png")),
                     np.int32)
    assert np.abs(seq - bat).max() <= 1


def test_rejected_unknown_flag():
    with pytest.raises(SystemExit):
        main(["replay", "--quiet", "--artifact", "x.npz", "--scheduler", "plms"])


def test_group_setup_shards_over_largest_divisor(tiny_pipe, capsys):
    """9 seeds on 8 visible devices must ride a 3-device dp mesh (largest
    divisor), not silently fall back to one device, and say so."""
    import jax

    from p2p_tpu.cli import _group_setup

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    seeds = list(range(9))
    ctx, lats, mesh = _group_setup(tiny_pipe, ["a cat"], seeds, None)
    assert lats.shape[0] == 9
    assert mesh is not None and mesh.devices.size == 3
    assert "sharding over 3" in capsys.readouterr().err

    # Divisible sweep keeps the full gate: 8 seeds -> 8 devices, no note.
    _, _, mesh8 = _group_setup(tiny_pipe, ["a cat"], list(range(8)), None)
    assert mesh8.devices.size == 8
    assert "sharding over" not in capsys.readouterr().err


def test_every_cli_preset_resolves_to_a_config():
    """Every preset choice (generate/edit/..., and `check`) derives from the
    one PRESET_CONFIGS map — includes sd21/sd21base (the v-prediction family
    the reference marks 'Not work', `/root/reference/main.py:27`)."""
    from p2p_tpu.cli import _preset_config, build_parser
    from p2p_tpu.models.checkpoint_check import PRESETS as CHECK_PRESETS
    from p2p_tpu.models.config import PRESET_CONFIGS

    parser = build_parser()
    subs = parser._subparsers._group_actions[0].choices
    gen = next(a for a in subs["generate"]._actions
               if "--preset" in a.option_strings)
    assert set(gen.choices) == set(PRESET_CONFIGS)
    assert {"sd21", "sd21base"} <= set(gen.choices)
    chk = next(a for a in subs["check"]._actions
               if "--preset" in a.option_strings)
    assert set(chk.choices) == set(CHECK_PRESETS)
    assert set(CHECK_PRESETS) == {k for k in PRESET_CONFIGS
                                  if not k.startswith("tiny")}
    for name in gen.choices:
        assert _preset_config(name).name
    # sd21 is the v-prediction variant.
    assert _preset_config("sd21").scheduler.prediction_type == "v_prediction"
