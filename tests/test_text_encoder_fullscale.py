"""Full-scale text-tower validation against a real `transformers` checkpoint.

Parity had only been proven at tiny scale with custom
configs — the residual risk being config-vs-checkpoint drift (e.g. SD-2.1's
23-layer truncation) that only real weight files would catch. No pretrained
weights exist in this image, but `transformers.CLIPTextModel` — the exact
class a diffusers checkpoint dir's `text_encoder/` holds
(`/root/reference/main.py:29`, `/root/reference/null_text.py:28`) — can be
instantiated at the *real* SD configs with random weights and
`save_pretrained`. That yields a genuine HF checkpoint directory (layout,
tensor names, shapes, and forward semantics all from the real library), so
these tests validate:

- strict load (every tensor mapped, both directions) of our SD14_TEXT /
  SD21_TEXT configs from real `model.safetensors` files at full scale;
- forward parity of the full-size towers vs `CLIPTextModel` (quick_gelu and
  the SD-2.1 gelu/23-layer variants).

Marked slow: builds ~123M/~290M-parameter models on the single-core host.
"""

import numpy as np
import pytest
import torch
import transformers

import jax

from p2p_tpu.models import init_text_encoder
from p2p_tpu.models.checkpoint import load_text_encoder
from p2p_tpu.models.config import SD14_TEXT, SD21_TEXT
from p2p_tpu.models.text_encoder import apply_text_encoder


def _hf_config(cfg):
    return transformers.CLIPTextConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.hidden_dim * cfg.ff_mult,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        max_position_embeddings=cfg.max_length,
        hidden_act=cfg.activation,
    )


@pytest.mark.slow
@pytest.mark.parametrize("cfg,label", [(SD14_TEXT, "sd14"), (SD21_TEXT, "sd21")])
def test_fullscale_strict_load_and_forward_parity(tmp_path, cfg, label):
    torch.manual_seed(0)
    model = transformers.CLIPTextModel(_hf_config(cfg)).eval()
    ckpt = tmp_path / label
    model.save_pretrained(str(ckpt))  # real HF layout: model.safetensors

    params = init_text_encoder(jax.random.PRNGKey(0), cfg)
    # strict=True: every checkpoint tensor must map, every mapped tensor must
    # exist with the right (transformed) shape — the full-scale name tables.
    params = load_text_encoder(params, cfg, str(ckpt), strict=True)

    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, size=(2, cfg.max_length), dtype=np.int64)
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).last_hidden_state.numpy()
    got = np.asarray(apply_text_encoder(params, cfg, ids.astype(np.int32)))
    # f32 end to end; differences are pure accumulation-order noise. The
    # tolerance is scaled for the 1024-wide 23-layer SD-2.1 tower.
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)
