"""Ring attention integrated into the U-Net: an ``sp`` mesh
axis shards large self-attention sites; the forward must match the
single-device program at tolerance on the 8-virtual-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from p2p_tpu.models import TINY, init_unet
from p2p_tpu.models.config import unet_layout
from p2p_tpu.models.unet import SpConfig, apply_unet


@pytest.fixture(scope="module")
def sp_mesh():
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    return Mesh(np.asarray(devs[:8]).reshape(8), ("sp",))


def test_ring_unet_matches_local(sp_mesh):
    """Full tiny U-Net forward with the 16²=256-pixel self sites sharded 8
    ways over sp equals the unsharded forward."""
    cfg = TINY.unet
    layout = unet_layout(cfg)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, cfg.sample_size, cfg.sample_size,
                              cfg.in_channels).astype(np.float32))
    ctx = jnp.asarray(rng.randn(2, cfg.context_len, cfg.context_dim)
                      .astype(np.float32))
    t = jnp.int32(500)

    eps_local, _ = jax.jit(
        lambda p, x, c: apply_unet(p, cfg, x, t, c, layout=layout))(params, x, ctx)

    sp = SpConfig(mesh=sp_mesh, axis="sp", min_pixels=256)

    eps_ring, _ = jax.jit(
        lambda p, x, c: apply_unet(p, cfg, x, t, c, layout=layout, sp=sp)
    )(params, x, ctx)

    np.testing.assert_allclose(np.asarray(eps_ring), np.asarray(eps_local),
                               atol=2e-5, rtol=1e-4)


def test_ring_unet_with_controller_keeps_edited_sites_local(sp_mesh):
    """Controller-touched sites must stay local (edits read whole probability
    rows); untouched large sites ride the ring. Output must still match the
    all-local program."""
    from p2p_tpu.controllers import factory
    from p2p_tpu.utils.tokenizer import HashWordTokenizer
    from p2p_tpu.controllers.base import init_store_state

    cfg = TINY.unet
    layout = unet_layout(cfg)
    params = init_unet(jax.random.PRNGKey(1), cfg)
    tok = HashWordTokenizer(model_max_length=cfg.context_len)
    prompts = ["a cat on a mat", "a dog on a mat"]
    # self_max_pixels=8²: the 16² self sites stay untouched -> ring-eligible.
    ctrl = factory.attention_replace(
        prompts, 4, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=8 * 8, max_len=cfg.context_len,
        store=False)

    rng = np.random.RandomState(1)
    b = 2 * len(prompts)
    x = jnp.asarray(rng.randn(b, cfg.sample_size, cfg.sample_size,
                              cfg.in_channels).astype(np.float32))
    ctx = jnp.asarray(rng.randn(b, cfg.context_len, cfg.context_dim)
                      .astype(np.float32))
    t = jnp.int32(300)
    state = init_store_state(layout, len(prompts))
    step = jnp.int32(1)

    def fwd(sp):
        eps, _ = jax.jit(
            lambda p, x, c, s: apply_unet(p, cfg, x, t, c, layout=layout,
                                          controller=ctrl, state=s, step=step,
                                          sp=sp))(params, x, ctx, state)
        return np.asarray(eps)

    sp = SpConfig(mesh=sp_mesh, axis="sp", min_pixels=256)
    np.testing.assert_allclose(fwd(sp), fwd(None), atol=2e-5, rtol=1e-4)


def test_text2image_with_sp_matches_unsharded(sp_mesh, tiny_pipe):
    """The full sampling engine with sp= (ring attention at the 16²-pixel
    self sites, 8-way) must reproduce the unsharded text2image images —
    the end-to-end long-context path, not just a single U-Net forward."""
    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import text2image

    tok = tiny_pipe.tokenizer
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    steps = 2
    # store=False: with the default store, every TINY self site (256 px,
    # under the 32² store cap) is controller-touched and the sp branch
    # would never compile — the test would compare identical programs.
    ctrl = factory.attention_replace(
        prompts, steps, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=8 * 8,
        max_len=TINY.text.max_length, store=False)
    rng = jax.random.PRNGKey(11)
    want, x_t, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=steps,
                              rng=rng)
    sp = SpConfig(mesh=sp_mesh, axis="sp", min_pixels=256)
    got, _, _ = text2image(tiny_pipe, prompts, ctrl, num_steps=steps,
                           latent=x_t, sp=sp)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1.0)


@pytest.mark.slow
def test_invert_with_sp_matches_unsharded(sp_mesh, tiny_pipe):
    """Null-text inversion under an sp plan (ring attention through BOTH
    compiled programs, including the optimization's gradient via the ring
    VJP) must match the unsharded inversion."""
    from p2p_tpu.engine.inversion import invert

    rng = np.random.RandomState(4)
    image = rng.randint(0, 256, (TINY.image_size, TINY.image_size, 3)
                        ).astype(np.uint8)
    kw = dict(num_steps=2, num_inner_steps=2)
    want = invert(tiny_pipe, image, "a cat riding a bike", **kw)
    sp = SpConfig(mesh=sp_mesh, axis="sp", min_pixels=256)
    got = invert(tiny_pipe, image, "a cat riding a bike", sp=sp, **kw)
    np.testing.assert_allclose(got.x_t, want.x_t, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got.uncond_embeddings,
                               want.uncond_embeddings, atol=1e-4, rtol=1e-3)


def test_alltoall_unet_matches_local(sp_mesh):
    """SpConfig(mode='alltoall') on a head-divisible axis: TINY has 2 heads,
    so a 2-device sp mesh uses all-to-all at the 256-pixel sites; the
    forward must match the unsharded program. On the 8-device mesh (heads
    2 % 8 != 0) every site must fall back to the ring — same answer."""
    cfg = TINY.unet
    layout = unet_layout(cfg)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, cfg.sample_size, cfg.sample_size,
                              cfg.in_channels).astype(np.float32))
    ctx = jnp.asarray(rng.randn(2, cfg.context_len, cfg.context_dim)
                      .astype(np.float32))
    t = jnp.int32(300)

    eps_local, _ = jax.jit(
        lambda p, x, c: apply_unet(p, cfg, x, t, c, layout=layout))(params, x, ctx)

    mesh2 = Mesh(np.asarray(jax.devices("cpu")[:2]).reshape(2), ("sp",))
    for mesh, label in ((mesh2, "alltoall"), (sp_mesh, "ring-fallback")):
        sp = SpConfig(mesh=mesh, axis="sp", min_pixels=256, mode="alltoall")

        def run(sp=sp):
            return jax.jit(
                lambda p, x, c: apply_unet(p, cfg, x, t, c, layout=layout,
                                           sp=sp))(params, x, ctx)

        if label == "ring-fallback":
            # Head-indivisible alltoall must say so: a user
            # benchmarking alltoall must not unknowingly measure ring.
            with pytest.warns(UserWarning, match="falls back to ring"):
                eps_sp, _ = run()
        else:
            eps_sp, _ = run()
        np.testing.assert_allclose(
            np.asarray(eps_sp), np.asarray(eps_local),
            atol=2e-5, rtol=1e-4, err_msg=label)


def test_spconfig_rejects_unknown_mode(sp_mesh):
    with pytest.raises(ValueError, match="unknown sp mode"):
        SpConfig(mesh=sp_mesh, axis="sp", mode="ulysses")


def test_sd14_hr_config_exists_with_ring_eligible_sites():
    """The >64² latent config (SURVEY §5 scaling axis): 128² latent has
    16384-pixel self sites — above SpConfig's default min_pixels."""
    from p2p_tpu.models import SD14_HR
    from p2p_tpu.models.config import unet_attn_specs

    specs = unet_attn_specs(SD14_HR.unet)
    big_self = [s for s in specs if not s[1] and s[2] ** 2 >= 64 * 64]
    assert len(big_self) >= 5
    assert SD14_HR.latent_size == 128
