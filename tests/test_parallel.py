"""Multi-device tests on the 8-virtual-CPU mesh: ring attention parity,
megatron param sharding, and the data-parallel sweep engine — the scale-out
surface the reference never had (SURVEY §2: parallelism introduced, not
ported)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.controllers import factory
from p2p_tpu.engine.sampler import encode_prompts
from p2p_tpu.models import TINY, unet_layout
from p2p_tpu.models.unet import apply_unet
from p2p_tpu.parallel import make_mesh, param_specs, seed_latents, shard_params, sweep
from p2p_tpu.parallel.ring import ring_self_attention


@pytest.fixture(scope="module")
def devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


def test_ring_attention_matches_single_device(devices):
    mesh = make_mesh(8, tp=1, axis_names=("sp", "unused"), devices=devices)
    b, h, s, d = 2, 4, 256, 16
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    scale = d ** -0.5

    ref_probs = jax.nn.softmax(
        jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale, axis=-1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", ref_probs, v)

    out = ring_self_attention(q, k, v, scale, mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_alltoall_attention_matches_single_device(devices):
    """Ulysses-style all-to-all sequence parallelism: head redistribution +
    one dense local attention must equal full attention."""
    from jax.sharding import Mesh
    from p2p_tpu.parallel import alltoall_self_attention
    from p2p_tpu.models import nn

    mesh = Mesh(np.asarray(devices[:4]).reshape(4), ("sp",))
    rng = np.random.RandomState(11)
    b, h, s, d = 2, 8, 64, 16  # h % 4 == 0, s % 4 == 0
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    scale = 1.0 / np.sqrt(d)
    want = jnp.einsum(
        "bhqk,bhkd->bhqd",
        nn.attention_probs(q, k, scale).astype(v.dtype), v)
    got = alltoall_self_attention(q, k, v, scale, mesh, "sp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_alltoall_attention_rejects_indivisible(devices):
    from jax.sharding import Mesh
    from p2p_tpu.parallel import alltoall_self_attention

    mesh = Mesh(np.asarray(devices[:4]).reshape(4), ("sp",))
    q = jnp.zeros((1, 6, 64, 8))  # 6 heads % 4 != 0
    with pytest.raises(ValueError, match="head count"):
        alltoall_self_attention(q, q, q, 1.0, mesh, "sp")
    q = jnp.zeros((1, 8, 62, 8))  # 62 pixels % 4 != 0
    with pytest.raises(ValueError, match="sequence length"):
        alltoall_self_attention(q, q, q, 1.0, mesh, "sp")


def test_ring_attention_rejects_indivisible(devices):
    mesh = make_mesh(8, tp=1, axis_names=("sp", "unused"), devices=devices)
    q = jnp.zeros((1, 1, 100, 8))
    with pytest.raises(ValueError):
        ring_self_attention(q, q, q, 1.0, mesh, axis_name="sp")


def test_tp_sharded_unet_matches_replicated(tiny_pipe, devices):
    """Megatron-sharded forward must be numerically identical (f32) to the
    single-device forward: XLA inserts the psums; the math cannot change."""
    cfg = TINY
    layout = unet_layout(cfg.unet)
    mesh = make_mesh(8, tp=2, devices=devices)
    params_tp = shard_params(tiny_pipe.unet_params, mesh)

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))

    @jax.jit
    def fwd(p, x, c):
        eps, _ = apply_unet(p, cfg.unet, x, jnp.int32(3), c, layout=layout)
        return eps

    ref = fwd(tiny_pipe.unet_params, x, ctx)
    out = fwd(params_tp, x, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_param_specs_shard_attention_kernels():
    specs = param_specs({"attn": {"to_q": {"kernel": jnp.zeros((8, 8))},
                                  "to_out": {"kernel": jnp.zeros((8, 8)),
                                             "bias": jnp.zeros((8,))}}},
                        tp_size=2)
    from jax.sharding import PartitionSpec as P
    assert specs["attn"]["to_q"]["kernel"] == P(None, "tp")
    assert specs["attn"]["to_out"]["kernel"] == P("tp", None)
    assert specs["attn"]["to_out"]["bias"] == P()


def test_dp_sweep_matches_sequential(tiny_pipe, devices):
    """G edit groups sharded over dp must produce the same images as running
    each group alone — for EVERY group, with a *different* controller per
    group (the sweep's claim is that edit parameters are traced leaves, so
    distinct equalizers/windows ride one compiled program)."""
    cfg = TINY
    tok = tiny_pipe.tokenizer
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    mesh = make_mesh(4, tp=1, devices=devices[:4])

    g = 4
    # Per-group differing traced leaves: equalizer scale AND self window.
    from p2p_tpu.align.words import get_equalizer

    ctrls_list = []
    for i, (scale, self_steps) in enumerate(
            zip((0.25, 1.0, 2.0, 5.0), (0.0, 0.5, 0.5, 1.0))):
        eq = get_equalizer(prompts[1], ("bike",), (scale,), tok)
        ctrls_list.append(factory.attention_reweight(
            prompts, 2, cross_replace_steps=0.8, self_replace_steps=self_steps,
            equalizer=eq, tokenizer=tok, self_max_pixels=64,
            max_len=cfg.text.max_length))
    ctrls = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls_list)

    ctx_c = encode_prompts(tiny_pipe, prompts)
    ctx_u = encode_prompts(tiny_pipe, [""] * 2)
    ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)
    ctx_g = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(3), g, 2, tiny_pipe.latent_shape)

    imgs, _ = sweep(tiny_pipe, ctx_g, lats, ctrls, num_steps=2, mesh=mesh)
    assert imgs.shape == (g, 2, cfg.image_size, cfg.image_size, 3)

    # Sequential oracle: every group alone, no mesh. Same math modulo XLA
    # reassociation — allow one uint8 level.
    for i in range(g):
        imgs1, _ = sweep(tiny_pipe,
                         ctx_g[i:i + 1], lats[i:i + 1],
                         jax.tree_util.tree_map(lambda x: x[i:i + 1], ctrls),
                         num_steps=2, mesh=None)
        np.testing.assert_allclose(
            np.asarray(imgs[i], np.float32), np.asarray(imgs1[0], np.float32),
            atol=1.0, err_msg=f"group {i} diverged from sequential run")

    # The controllers genuinely differ: extreme equalizer groups must not
    # produce identical edited images.
    assert not np.array_equal(np.asarray(imgs[0][1]), np.asarray(imgs[3][1]))


def test_sweep_dpm_scheduler_matches_text2image(tiny_pipe):
    """sweep(scheduler="dpm") must match the single-group text2image DPM
    path on the same latent and controller."""
    from p2p_tpu.engine.sampler import text2image

    cfg = TINY
    tok = tiny_pipe.tokenizer
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    steps = 3
    ctrl = factory.attention_replace(
        prompts, steps, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=64, max_len=cfg.text.max_length)

    base = jax.random.normal(jax.random.PRNGKey(5),
                             (1,) + tiny_pipe.latent_shape, jnp.float32)
    want, _, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=steps,
                            scheduler="dpm", latent=base)

    ctx_c = encode_prompts(tiny_pipe, prompts)
    ctx_u = encode_prompts(tiny_pipe, [""] * 2)
    ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)[None]
    lats = jnp.broadcast_to(base, (1, 2) + tiny_pipe.latent_shape)
    ctrls = jax.tree_util.tree_map(lambda x: x[None], ctrl)
    got, _ = sweep(tiny_pipe, ctx, lats, ctrls, num_steps=steps,
                   scheduler="dpm", mesh=None)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want, np.float32), atol=1.0)


def test_multihost_helpers_single_process(devices):
    """Single-process degradation: initialize() is a no-op, global_mesh
    covers the local devices, process_groups spans everything."""
    from p2p_tpu.parallel import multihost

    assert multihost.initialize() is False  # no coordinator configured
    mesh = multihost.global_mesh(tp=2)
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] * 2 == len(jax.devices())
    assert list(multihost.process_groups(5)) == [0, 1, 2, 3, 4]
    with pytest.raises(ValueError):
        multihost.global_mesh(tp=3)


def test_dp_sweep_with_local_blend(tiny_pipe, devices):
    """LocalBlend (store-consuming, latent-compositing) under the vmapped dp
    sweep must match the sequential run — the store state rides the vmap."""
    cfg = TINY
    tok = tiny_pipe.tokenizer
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    mesh = make_mesh(2, tp=1, devices=devices[:2])
    g = 2
    lb = factory.local_blend(prompts, ["cat", "dog"], tok, num_steps=2,
                             resolution=8, max_len=cfg.text.max_length)
    ctrl = factory.attention_replace(
        prompts, 2, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, local_blend=lb, self_max_pixels=64,
        max_len=cfg.text.max_length)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (g,) + x.shape), ctrl)

    ctx_c = encode_prompts(tiny_pipe, prompts)
    ctx_u = encode_prompts(tiny_pipe, [""] * 2)
    ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)
    ctx_g = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(9), g, 2, tiny_pipe.latent_shape)

    imgs, _ = sweep(tiny_pipe, ctx_g, lats, ctrls, num_steps=2, mesh=mesh)
    imgs0, _ = sweep(tiny_pipe, ctx_g[:1], lats[:1],
                     jax.tree_util.tree_map(lambda x: x[:1], ctrls),
                     num_steps=2, mesh=None)
    np.testing.assert_allclose(np.asarray(imgs[0], np.float32),
                               np.asarray(imgs0[0], np.float32), atol=1.0)


def test_dp_sweep_replays_inversion_artifact(tiny_pipe, devices):
    """A null-text inversion artifact's edit sweep rides the dp engine:
    per-group per-step uncond embeddings substituted inside the vmapped scan must reproduce the sequential
    ``text2image(uncond_embeddings=...)`` replay for every group — across
    all 8 virtual devices, with a different edit controller per group."""
    from p2p_tpu.engine.inversion import invert
    from p2p_tpu.engine.sampler import text2image

    cfg = TINY
    tok = tiny_pipe.tokenizer
    steps = 2
    rng = np.random.default_rng(7)
    image = rng.integers(0, 256, (cfg.image_size, cfg.image_size, 3),
                         dtype=np.uint8)
    art = invert(tiny_pipe, image, "a cat riding a bike", num_steps=steps,
                 num_inner_steps=2)

    prompts = ["a cat riding a bike", "a dog riding a bike"]
    g = 8
    mesh = make_mesh(8, tp=1, devices=devices)
    # Distinct traced edit windows per group: the whole artifact sweep is
    # one compiled program over 8 devices.
    ctrls_list = [
        factory.attention_replace(
            prompts, steps, cross_replace_steps=0.8,
            self_replace_steps=s, tokenizer=tok, self_max_pixels=64,
            max_len=cfg.text.max_length)
        for s in np.linspace(0.0, 1.0, g)
    ]
    ctrls = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ctrls_list)

    ctx_c = encode_prompts(tiny_pipe, prompts)
    ctx_u = encode_prompts(tiny_pipe, [""] * 2)
    ctx_g = jnp.broadcast_to(
        jnp.concatenate([ctx_u, ctx_c], axis=0)[None],
        (g,) + (2 * len(prompts), ctx_c.shape[1], ctx_c.shape[2]))
    x_t = jnp.asarray(art.x_t)
    lats = jnp.broadcast_to(x_t[None], (g, len(prompts)) + x_t.shape[1:])
    ups = jnp.broadcast_to(
        jnp.asarray(art.uncond_embeddings)[None],
        (g,) + art.uncond_embeddings.shape)

    imgs, _ = sweep(tiny_pipe, ctx_g, lats, ctrls, num_steps=steps,
                    mesh=mesh, uncond_per_step=ups)
    assert imgs.shape == (g, 2, cfg.image_size, cfg.image_size, 3)

    # Sequential oracle: the existing single-group replay path.
    for i in (0, 3, 7):
        img1, _, _ = text2image(
            tiny_pipe, prompts, ctrls_list[i], num_steps=steps, latent=x_t,
            uncond_embeddings=jnp.asarray(art.uncond_embeddings))
        np.testing.assert_allclose(
            np.asarray(imgs[i], np.float32), np.asarray(img1, np.float32),
            atol=1.0, err_msg=f"group {i} diverged from sequential replay")

    # The optimized embeddings actually flow: dropping them changes output.
    imgs_raw, _ = sweep(tiny_pipe, ctx_g, lats, ctrls, num_steps=steps,
                        mesh=mesh)
    assert not np.array_equal(np.asarray(imgs), np.asarray(imgs_raw))


def test_dp_sweep_uncond_per_step_validation(tiny_pipe):
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    ctx_c = encode_prompts(tiny_pipe, prompts)
    ctx_u = encode_prompts(tiny_pipe, [""] * 2)
    ctx_g = jnp.concatenate([ctx_u, ctx_c], axis=0)[None]
    lats = seed_latents(jax.random.PRNGKey(0), 1, 2, tiny_pipe.latent_shape)
    ups = jnp.zeros((1, 2, 1, ctx_c.shape[1], ctx_c.shape[2]))
    with pytest.raises(ValueError, match="ddim"):
        sweep(tiny_pipe, ctx_g, lats, None, num_steps=2, scheduler="dpm",
              uncond_per_step=ups)
    with pytest.raises(ValueError, match="steps"):
        sweep(tiny_pipe, ctx_g, lats, None, num_steps=3,
              uncond_per_step=ups)
    with pytest.raises(ValueError, match="G, T, 1, L, D"):
        sweep(tiny_pipe, ctx_g, lats, None, num_steps=2,
              uncond_per_step=ups[0])


def test_artifact_replay_inputs_shapes_and_validation(tiny_pipe):
    from p2p_tpu.parallel import artifact_replay_inputs

    cfg = tiny_pipe.config
    tok = tiny_pipe.tokenizer
    steps = 2
    targets = ["a dog riding a bike", "a fox riding a bike"]
    ctrls_list = [factory.attention_replace(
        ["a cat riding a bike", t], steps, cross_replace_steps=0.8,
        self_replace_steps=0.4, tokenizer=tok, self_max_pixels=64,
        max_len=cfg.text.max_length) for t in targets]
    x_t = np.zeros((1,) + tiny_pipe.latent_shape, np.float32)
    ups = np.zeros((steps, 1, cfg.text.max_length, cfg.text.hidden_dim),
                   np.float32)
    ctx_g, lats, ups_g, ctrls = artifact_replay_inputs(
        tiny_pipe, x_t, ups, "a cat riding a bike", targets, ctrls_list)
    L, D = ctx_g.shape[-2:]
    assert ctx_g.shape == (2, 4, L, D)       # (G, 2B) with B=2
    assert lats.shape == (2, 2) + tiny_pipe.latent_shape
    assert ups_g.shape == (2,) + ups.shape
    # The uncond rows are the "" encoding; cond row 0 is the source (helper
    # encodes all prompts in ONE forward — batch-size reassociation only).
    enc = encode_prompts(tiny_pipe, ["", "a cat riding a bike"])
    np.testing.assert_allclose(np.asarray(ctx_g[0][0]), np.asarray(enc[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ctx_g[1][2]), np.asarray(enc[1]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ctx_g[0][0]),
                                  np.asarray(ctx_g[1][0]))
    with pytest.raises(ValueError, match="controllers"):
        artifact_replay_inputs(tiny_pipe, x_t, ups, "a cat riding a bike",
                               targets, ctrls_list[:1])
