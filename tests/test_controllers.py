"""Controller algebra tests: hand-computed oracles for Replace/Refine/Reweight,
store accumulation math, identity guarantees, and LocalBlend masking checked
against a torch-CPU oracle for the pooling/interpolation steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.controllers import (
    Controller,
    StoreConfig,
    apply_attention_control,
    apply_step_callback,
    attention_refine,
    attention_replace,
    attention_reweight,
    attention_store,
    average_attention,
    build_layout,
    empty_control,
    init_store_state,
    local_blend,
    make_controller,
    spatial_replace,
)
from p2p_tpu.controllers.edit import EditParams, edit_cross_attention, edit_self_attention

L = 16  # token length for tests
HEADS = 2
E = 2   # edit prompts
B = 1 + E


def tiny_layout(store_cfg=None):
    # (place, is_cross, resolution, heads, key_len) — a miniature U-Net:
    # down 8² (cross+self), mid 4², up 8²×2 — all storeable at max_pixels=64.
    specs = [
        ("down", True, 8, HEADS, L), ("down", False, 8, HEADS, 64),
        ("mid", True, 4, HEADS, L), ("mid", False, 4, HEADS, 16),
        ("up", True, 8, HEADS, L), ("up", False, 8, HEADS, 64),
    ]
    return build_layout(specs, store_cfg or StoreConfig(max_pixels=64))


def rand_attn(key, meta, batch=2 * B):
    a = jax.random.uniform(key, (batch, meta.heads, meta.pixels, meta.key_len))
    return a / a.sum(-1, keepdims=True)


def alpha_all_on(num_steps=4):
    return jnp.ones((num_steps + 1, E, 1, 1, L))


# ---------------------------------------------------------------------------
# edit math oracles
# ---------------------------------------------------------------------------


def test_replace_einsum_matches_numpy():
    key = jax.random.PRNGKey(0)
    base = jax.random.uniform(key, (HEADS, 10, L))
    edits = jax.random.uniform(jax.random.PRNGKey(1), (E, HEADS, 10, L))
    mapper = jax.random.uniform(jax.random.PRNGKey(2), (E, L, L))
    p = EditParams(cross_alpha=alpha_all_on(), mapper=mapper, kind="replace")
    got = edit_cross_attention(p, base, edits, jnp.int32(0))
    want = np.einsum("hpw,ewn->ehpn", np.asarray(base), np.asarray(mapper))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_refine_gather_matches_numpy():
    base = jax.random.uniform(jax.random.PRNGKey(0), (HEADS, 10, L))
    edits = jax.random.uniform(jax.random.PRNGKey(1), (E, HEADS, 10, L))
    mapper = np.stack([np.roll(np.arange(L), 1), np.arange(L)]).astype(np.int32)
    mapper[0, 3] = -1  # a "new token" position; alpha must kill it
    alphas = np.ones((E, L), dtype=np.float32)
    alphas[0, 3] = 0.0
    p = EditParams(
        cross_alpha=alpha_all_on(), mapper=jnp.asarray(mapper),
        refine_alphas=jnp.asarray(alphas)[:, None, None, :], kind="refine",
    )
    got = np.asarray(edit_cross_attention(p, base, edits, jnp.int32(0)))
    bn, en = np.asarray(base), np.asarray(edits)
    want = np.empty_like(en)
    for e in range(E):
        gathered = bn[:, :, mapper[e]]  # negative index wraps like torch
        want[e] = gathered * alphas[e][None, None, :] + en[e] * (1 - alphas[e][None, None, :])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the -1 position fell through to the edit prompt's own attention
    np.testing.assert_allclose(got[0][:, :, 3], en[0][:, :, 3], rtol=1e-6)


def test_reweight_scales_and_chains():
    base = jax.random.uniform(jax.random.PRNGKey(0), (HEADS, 10, L))
    edits = jax.random.uniform(jax.random.PRNGKey(1), (E, HEADS, 10, L))
    eq = jnp.ones((E, L)).at[:, 5].set(3.0)
    # pure reweight: base broadcast * equalizer
    p = EditParams(cross_alpha=alpha_all_on(), equalizer=eq, kind="none")
    got = np.asarray(edit_cross_attention(p, base, edits, jnp.int32(0)))
    want = np.broadcast_to(np.asarray(base)[None], got.shape) * np.asarray(eq)[:, None, None, :]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # chained on replace: transform first, then scale (main.py:258-263)
    mapper = jax.random.uniform(jax.random.PRNGKey(2), (E, L, L))
    p2 = EditParams(cross_alpha=alpha_all_on(), mapper=mapper, equalizer=eq, kind="replace")
    got2 = np.asarray(edit_cross_attention(p2, base, edits, jnp.int32(0)))
    want2 = np.einsum("hpw,ewn->ehpn", np.asarray(base), np.asarray(mapper)) \
        * np.asarray(eq)[:, None, None, :]
    np.testing.assert_allclose(got2, want2, rtol=1e-5)


def test_cross_alpha_schedule_blends():
    base = jax.random.uniform(jax.random.PRNGKey(0), (HEADS, 4, L))
    edits = jax.random.uniform(jax.random.PRNGKey(1), (E, HEADS, 4, L))
    alpha = jnp.zeros((5, E, 1, 1, L)).at[0].set(1.0)  # on at step 0 only
    mapper = jnp.stack([jnp.eye(L)] * E)
    p = EditParams(cross_alpha=alpha, mapper=mapper, kind="replace")
    at0 = edit_cross_attention(p, base, edits, jnp.int32(0))
    at3 = edit_cross_attention(p, base, edits, jnp.int32(3))
    np.testing.assert_allclose(np.asarray(at0), np.broadcast_to(np.asarray(base)[None], at0.shape), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(at3), np.asarray(edits), rtol=1e-6)


def test_self_attention_window_and_size_gate():
    base = jax.random.uniform(jax.random.PRNGKey(0), (HEADS, 16, 16))
    edits = jax.random.uniform(jax.random.PRNGKey(1), (E, HEADS, 16, 16))
    p = EditParams(cross_alpha=alpha_all_on(), kind="none",
                   self_start=1, self_end=3, self_max_pixels=16)
    inside = edit_self_attention(p, base, edits, jnp.int32(2), pixels=16)
    outside = edit_self_attention(p, base, edits, jnp.int32(3), pixels=16)
    np.testing.assert_allclose(np.asarray(inside),
                               np.broadcast_to(np.asarray(base)[None], inside.shape), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(outside), np.asarray(edits), rtol=1e-6)
    # maps larger than self_max_pixels are never touched (main.py:170)
    big = edit_self_attention(p, base, edits, jnp.int32(2), pixels=64)
    np.testing.assert_allclose(np.asarray(big), np.asarray(edits), rtol=1e-6)


# ---------------------------------------------------------------------------
# hook plumbing: store, identity, uncond-half invariance
# ---------------------------------------------------------------------------


def test_identity_controller_is_noop_and_free():
    layout = tiny_layout()
    meta = layout.metas[0]
    attn = rand_attn(jax.random.PRNGKey(0), meta)
    state = ()
    c = empty_control()
    s2, out = apply_attention_control(c, meta, state, attn, jnp.int32(0))
    assert out is attn and s2 is state  # literally the same object: zero ops
    s3, out3 = apply_attention_control(None, meta, state, attn, jnp.int32(0))
    assert out3 is attn


def test_store_accumulates_cond_half():
    layout = tiny_layout()
    tok_steps = 3
    c = attention_store()
    state = init_store_state(layout, batch_cond=B)
    metas = layout.metas
    attns = {m.layer_idx: rand_attn(jax.random.PRNGKey(m.layer_idx), m) for m in metas}
    for step in range(tok_steps):
        for m in metas:
            state, out = apply_attention_control(c, m, state, attns[m.layer_idx], jnp.int32(step))
            np.testing.assert_array_equal(np.asarray(out), np.asarray(attns[m.layer_idx]))
    avg = average_attention(layout, state, tok_steps)
    m0 = metas[0]
    np.testing.assert_allclose(
        np.asarray(avg["down_cross"][0]),
        np.asarray(attns[0][B:]),  # cond half, averaged over identical steps
        rtol=1e-5,
    )
    assert len(avg["mid_cross"]) == 1 and len(avg["up_self"]) == 1


def test_store_holds_post_edit_maps(tokenizer):
    """The reference's store aliases the tensor the edit mutates in place
    (main.py:132 append + main.py:193 in-place write), so stored edit rows are
    post-edit; the base row is untouched."""
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    c = attention_replace(prompts, 4, 1.0, 1.0, tokenizer, max_len=L)
    c = Controller(edit=c.edit, store=True)
    state = init_store_state(layout, batch_cond=B)
    meta = layout.metas[0]  # cross
    attn = rand_attn(jax.random.PRNGKey(7), meta)
    state, out = apply_attention_control(c, meta, state, attn, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(out[B:]), rtol=1e-6)
    assert not np.allclose(np.asarray(state[0][1]), np.asarray(attn[B + 1]))
    np.testing.assert_allclose(np.asarray(state[0][0]), np.asarray(attn[B]), rtol=1e-6)


def test_reweight_inherits_blend_from_editless_base(tokenizer):
    from p2p_tpu.controllers import attention_reweight, local_blend as mk_blend

    prompts = ["a cat sat", "a dog sat"]
    lb = mk_blend(prompts, ["cat", "dog"], tokenizer, num_steps=4, resolution=8, max_len=L)
    base = Controller(blend=lb, store=True)
    eq = np.ones((1, L), dtype=np.float32)
    c = attention_reweight(prompts, 4, 1.0, 0.0, eq, tokenizer, base=base)
    assert c.blend is not None


def test_uncond_half_never_edited(tokenizer):
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    c = attention_replace(prompts, 4, 1.0, 1.0, tokenizer, max_len=L)
    state = init_store_state(layout, batch_cond=B)
    meta = layout.metas[0]  # cross
    attn = rand_attn(jax.random.PRNGKey(5), meta)
    state, out = apply_attention_control(c, meta, state, attn, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(out[:B]), np.asarray(attn[:B]))
    np.testing.assert_array_equal(np.asarray(out[B]), np.asarray(attn[B]))  # base prompt row
    assert not np.allclose(np.asarray(out[B + 1]), np.asarray(attn[B + 1]))


def test_zero_replace_steps_equals_baseline(tokenizer):
    """cross/self_replace_steps=0 must leave attention untouched
    (hyperparameter notes at /root/reference/main.py:448-460)."""
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    # applied past ``text2image``, where a bound nobody gave is taken against
    # the model: the paper's 16² is given here (this miniature has 8² and 4²)
    c = attention_replace(prompts, 4, 0.0, 0.0, tokenizer, max_len=L,
                          self_max_pixels=16 * 16)
    state = init_store_state(layout, batch_cond=B)
    for m in layout.metas:
        attn = rand_attn(jax.random.PRNGKey(m.layer_idx), m)
        state, out = apply_attention_control(c, m, state, attn, jnp.int32(2))
        np.testing.assert_allclose(np.asarray(out), np.asarray(attn), atol=1e-6)


def test_spatial_replace_injects_then_stops():
    layout = tiny_layout()
    c = spatial_replace(num_steps=10, stop_inject=0.6)  # inject for first 4 steps
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 8, 8, 4))
    early = apply_step_callback(c, layout, (), x, jnp.int32(1))
    late = apply_step_callback(c, layout, (), x, jnp.int32(5))
    np.testing.assert_allclose(np.asarray(early), np.broadcast_to(np.asarray(x[:1]), x.shape))
    np.testing.assert_array_equal(np.asarray(late), np.asarray(x))


# ---------------------------------------------------------------------------
# LocalBlend vs torch oracle
# ---------------------------------------------------------------------------


def torch_blend_oracle(maps, alpha, x_t_nchw, th, start_ok=True):
    """The reference blend math (/root/reference/null_text.py:41-69) on torch CPU."""
    import torch
    import torch.nn.functional as nnf

    # np.array: writable copies — torch.from_numpy warns on the read-only
    # views jax hands out.
    maps = torch.from_numpy(np.array(maps))     # (B, SH, res, res, L)
    alpha = torch.from_numpy(np.array(alpha))   # (B, 1, 1, 1, L)
    x_t = torch.from_numpy(np.array(x_t_nchw))  # (B, C, H, W)
    m = (maps * alpha).sum(-1).mean(1, keepdim=True)  # (B, 1, res, res)
    m = nnf.max_pool2d(m, (3, 3), (1, 1), padding=(1, 1))
    m = nnf.interpolate(m, size=x_t.shape[2:])
    m = m / m.max(2, keepdims=True)[0].max(3, keepdims=True)[0]
    m = m.gt(th)
    m = (m[:1] + m).float()
    out = x_t[:1] + m * (x_t - x_t[:1])
    return out.numpy()


def test_local_blend_matches_torch_oracle(tokenizer):
    torch = pytest.importorskip("torch")  # noqa: F841
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    lb = local_blend(prompts, ["cat", "dog", "pig"], tokenizer,
                     num_steps=4, resolution=8, max_len=L)
    c = Controller(blend=lb)
    state = init_store_state(layout, batch_cond=B)
    rng = np.random.RandomState(0)
    # accumulate two steps of maps through the hook
    for step in range(2):
        for m in layout.metas:
            attn = jnp.asarray(rng.rand(2 * B, m.heads, m.pixels, m.key_len).astype(np.float32))
            state, _ = apply_attention_control(c, m, state, attn, jnp.int32(step))
    x_nhwc = rng.randn(B, 16, 16, 4).astype(np.float32)
    got = apply_step_callback(c, layout, state, jnp.asarray(x_nhwc), jnp.int32(1))

    # oracle input: stored cross maps at res 8, concatenated over slots on the head axis
    blend_metas = layout.blend_metas(8)
    maps = np.concatenate(
        [np.asarray(state[m.store_slot]).reshape(B, HEADS, 8, 8, L) for m in blend_metas],
        axis=1,
    )
    alpha = np.asarray(lb.alpha_layers)[:, None, None, None, :]
    want_nchw = torch_blend_oracle(maps, alpha, x_nhwc.transpose(0, 3, 1, 2), float(lb.th_pool))
    np.testing.assert_allclose(
        np.asarray(got).transpose(0, 3, 1, 2), want_nchw, rtol=1e-4, atol=1e-5
    )


def test_local_blend_start_blend_warmup(tokenizer):
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    lb = local_blend(prompts, ["cat", "dog", "pig"], tokenizer,
                     start_blend=0.5, num_steps=4, resolution=8, max_len=L)
    c = Controller(blend=lb)
    state = init_store_state(layout, batch_cond=B)
    for m in layout.metas:
        attn = rand_attn(jax.random.PRNGKey(m.layer_idx), m)
        state, _ = apply_attention_control(c, m, state, attn, jnp.int32(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 16, 16, 4))
    early = apply_step_callback(c, layout, state, x, jnp.int32(0))  # 0+1 <= 2: off
    late = apply_step_callback(c, layout, state, x, jnp.int32(2))   # 2+1 > 2: on
    np.testing.assert_array_equal(np.asarray(early), np.asarray(x))
    assert not np.array_equal(np.asarray(late), np.asarray(x))
    # source latent is never modified by blending
    np.testing.assert_allclose(np.asarray(late[0]), np.asarray(x[0]), atol=1e-6)


def test_make_controller_assembles(tokenizer):
    prompts = ["a cat sat on the mat", "a dog sat on the mat"]
    c = make_controller(prompts, True, 0.8, 0.4, tokenizer, num_steps=10,
                        blend_words=[["cat"], ["dog"]],
                        equalizer_params={"words": "dog", "values": [2.0]})
    assert c.edit is not None and c.edit.kind == "replace"
    assert c.edit.equalizer is not None
    assert c.blend is not None and c.blend.start_blend == 2
    assert c.edit.self_start == 0 and c.edit.self_end == 4


def test_controller_is_pytree_and_jittable(tokenizer):
    layout = tiny_layout()
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    c = attention_replace(prompts, 4, 0.8, 0.4, tokenizer, max_len=L)
    meta = layout.metas[0]
    attn = rand_attn(jax.random.PRNGKey(0), meta)
    state = init_store_state(layout, batch_cond=B)

    @jax.jit
    def f(ctrl, st, a, step):
        return apply_attention_control(ctrl, meta, st, a, step)

    s1, o1 = f(c, state, attn, jnp.int32(0))
    s2, o2 = apply_attention_control(c, meta, state, attn, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1[0]), np.asarray(s2[0]), rtol=1e-6)


# ---------------------------------------------------------------------------
# injected self sites: the base row's q and k, no map (ISSUE 37)
# ---------------------------------------------------------------------------


def _injecting(kind, tokenizer, pixels):
    """A ``kind`` controller over three prompts (two edit rows) whose self
    window is steps [0, 2) of 4 and takes maps up to ``pixels``; no store."""
    prompts = ["a cat sat", "a dog sat", "a pig sat"]
    args, kw = (prompts, 4, 0.8, 0.5), dict(self_max_pixels=pixels, max_len=L,
                                             store=False)
    if kind == "reweight":
        return attention_reweight(*args, jnp.full((E, L), 2.0), tokenizer, **kw)
    return {"replace": attention_replace,
            "refine": attention_refine}[kind](*args, tokenizer, **kw)


@pytest.mark.parametrize("site", ["einsum", "kernel"])
@pytest.mark.parametrize("step", [0, 3], ids=["in-window", "after-window"])
@pytest.mark.parametrize("kind", ["replace", "refine", "reweight"])
def test_injected_self_site_equals_the_materialized_path(tokenizer, monkeypatch,
                                                         kind, step, site):
    """A self site the controller only injects into, at a shape the flash
    kernel takes (32², 1,024 keys; the interpreter stands in for the chip),
    runs the kernel on the base row's q and k in its edit rows
    (``controllers.edit.inject_self_operands``), notes the kernel's tile, and
    gives the site output the materialized path gives (``attention_probs`` →
    ``apply_attention_control`` → P·V) to the bf16 operands' rounding. Below
    1,024 keys (8², as `sd14`'s and `sd21`'s window sites) the site keeps the
    materialized path, the parent's program; the substitution on the einsum
    chain gives its rows bit for bit all the same."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from p2p_tpu.controllers.base import AttnMeta, controller_only_injects
    from p2p_tpu.controllers.edit import inject_self_operands
    from p2p_tpu.models import nn, unet
    from p2p_tpu.obs import launches

    side, heads, dim = (8, HEADS, 32) if site == "einsum" else (32, 1, 64)
    meta = AttnMeta(0, "mid", False, side, heads, side * side)
    ctrl = _injecting(kind, tokenizer, meta.pixels)
    assert int(ctrl.edit.self_start) <= 0 < int(ctrl.edit.self_end) <= 3
    assert controller_only_injects(ctrl, meta)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    p, ln = unet._attn_init(k1, dim, dim, dim), unet.nn.norm_init(dim)
    x = jax.random.normal(k2, (2 * B, meta.pixels, dim))
    monkeypatch.setattr(nn, "_on_tpu", lambda: True)

    def site_fn(x, ctrl=ctrl):
        ctx = unet._HookCtx(None, ctrl, (), jnp.int32(step), ("off",))
        with force_tpu_interpret_mode():
            return unet._attention_site(p, ln, x, x, heads, ctx, meta, False)

    def run(ctrl=ctrl):
        launches.built()
        return np.asarray(site_fn(x, ctrl)), launches._traced_sites[0]

    new, noted = run()
    new_program = str(jax.make_jaxpr(site_fn)(x))
    monkeypatch.setattr(unet, "controller_only_injects", lambda c, m: False)
    old, old_noted = run()
    assert noted.how == old_noted.how == "edited" and old_noted.geometry is None
    if site == "einsum":
        assert noted.geometry is None
        assert new_program == str(jax.make_jaxpr(site_fn)(x))
        q, k, v = jax.random.normal(k1, (3, 2 * B, heads, meta.pixels, dim // heads))
        scale = (dim // heads) ** -0.5
        _, probs = apply_attention_control(ctrl, meta, (), nn.attention_probs(q, k, scale),
                                           jnp.int32(step))
        want = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        qs, ks = inject_self_operands(ctrl.edit, q, k, jnp.int32(step))
        np.testing.assert_array_equal(np.asarray(nn.fused_attention(qs, ks, v, scale)),
                                      np.asarray(want))
        return
    assert (noted.geometry, noted.operand) == ((1024, 1024, 1024), "bfloat16")
    np.testing.assert_allclose(new, old, atol=2e-3, rtol=0)    # 3e-4 read
    # not vacuous: the edit rows leave plain attention inside the window only
    plain = run(None)[0]
    np.testing.assert_array_equal(new[:B + 1], plain[:B + 1])
    moved = np.abs(new[B + 1:] - plain[B + 1:]).max()
    assert moved > 0.05 if step == 0 else moved == 0
