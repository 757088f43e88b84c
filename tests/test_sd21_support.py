"""What `sd21` (SD-2.1 at 768², PR 29) asks of the program, at toy sizes on
the CPU: v-prediction against the plain reference through ``text2image``
(ungated and gated), the same path from a v-model and an ε-model under every
scheduler and through null-text inversion, controller defaults taken from
the model's layout, and what a launch keeps about its self-attention sites
and its controller's store."""

import contextlib
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import check as check_mod
from benchmarks.lib import harness, pipeline
from p2p_tpu.controllers import factory
from p2p_tpu.controllers.base import PaperLevel, controller_touches
from p2p_tpu.engine import inversion, sampler
from p2p_tpu.engine.sampler import Pipeline, text2image
from p2p_tpu.models import LDM256, SD14, SD14_HR, SD21, SD21_BASE, TINY, TINY_LDM, TINY_V
from p2p_tpu.models import unet as unet_mod
from p2p_tpu.models.config import SchedulerConfig, unet_layout
from p2p_tpu.obs import launches
from p2p_tpu.ops import schedulers as sched_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_V_JSON = os.path.join(ROOT, "tests", "benchmark", "rehearsal_v", "bench",
                           "configs", "tiny_v.json")
PROMPTS = ("a red cat eating a burger in the forest",
           "a red dog eating a burger in the forest")
KEY = (20260929, 7)
EDIT = {"kind": "replace", "num_steps": 4, "guidance_scale": 7.5,
        "cross_replace_steps": 0.8, "self_replace_steps": 0.4,
        "self_max_pixels": 36}


# -- (a) the tiny v-prediction preset against the plain reference -----------

@pytest.fixture(scope="module")
def tiny_v():
    """The toy v-prediction preset (heads by ``head_dim``, latent levels
    12 / 6 / 3) with the benchmark's seeded weights, and its reference."""
    with open(TINY_V_JSON) as f:
        config = json.load(f)
    pipe, weights = pipeline.build(config, 2147483659)
    return config, pipe, weights, harness.load_module("reference", "latent_diffusion_v")


def _program(pipe, gate=None, dtype=jnp.float32):
    ctrl = factory.attention_replace(
        list(PROMPTS), EDIT["num_steps"], EDIT["cross_replace_steps"],
        EDIT["self_replace_steps"], pipe.tokenizer,
        self_max_pixels=EDIT["self_max_pixels"],
        max_len=pipe.config.text.max_length, store=True)
    images, _, _ = text2image(
        pipe, list(PROMPTS), ctrl, num_steps=EDIT["num_steps"],
        guidance_scale=EDIT["guidance_scale"], scheduler="ddim",
        rng=jnp.asarray(KEY, jnp.uint32), gate=gate, dtype=dtype)
    return np.asarray(images)


def _reference(tiny_v, gate=None):
    config, pipe, weights, ref = tiny_v
    edit = dict(EDIT, gate=gate)
    x_T = ref.noise(KEY, (1,) + pipe.latent_shape)
    align = {k: jnp.asarray(v) for k, v in ref.alignment(config, edit, PROMPTS).items()}
    img, _ = ref.make_edit_fn(config, edit)(
        weights, x_T, jnp.asarray(ref.prompt_ids(config, PROMPTS)), align)
    return np.asarray(ref.to_uint8(img))


def _err(served, reference):
    return max(check_mod.image_rel_err(served[j], reference[j])
               for j in range(len(reference)))


#: Both sides compute in float32 on the CPU, so they differ by the order of
#: their sums alone: a handful of the 6,912 uint8 values of an image land one
#: level apart, 3e-6 each over a standard deviation of ~45 (readings 6e-6 to
#: 1e-5 on five seeds). 2e-4 leaves room for sixty such values and stands
#: three orders under the least fault below.
LIMIT = 2e-4


@pytest.mark.parametrize("gate", [None, 0.5], ids=["ungated", "gated"])
def test_tiny_v_matches_the_plain_reference(tiny_v, gate):
    """v-prediction through DDIM, guidance and the attention control; gated,
    through the phase-2 extrapolation of the residual cached in v-space."""
    _, pipe, _, _ = tiny_v
    assert pipe.config is TINY_V
    assert pipe.config.scheduler.prediction_type == "v_prediction"
    assert [pipe.config.unet.resolution_at(i) for i in range(3)] == [12, 6, 3]
    with pytest.warns(UserWarning) if gate else contextlib.nullcontext():
        served = _program(pipe, gate)
    assert _err(served, _reference(tiny_v, gate)) < LIMIT


def test_the_comparison_is_tight_enough(tiny_v):
    """ε in v's place, and bfloat16 arrays, each fail the limit by orders."""
    _, pipe, _, _ = tiny_v
    reference = _reference(tiny_v)
    eps_cfg = dataclasses.replace(
        pipe.config, scheduler=dataclasses.replace(
            pipe.config.scheduler, prediction_type="epsilon"))
    as_eps = _program(dataclasses.replace(pipe, config=eps_cfg))
    assert _err(as_eps, reference) > 100 * LIMIT
    assert _err(_program(pipe, dtype=jnp.bfloat16), reference) > 100 * LIMIT
    # and the reference told ε disagrees with the program as well
    config, _, weights, ref = tiny_v
    eps_ref = _reference((dict(config, prediction_type="epsilon"), pipe, weights, ref))
    assert _err(_program(pipe), eps_ref) > 100 * LIMIT


# -- (b) a v-model made from a known ε samples the ε-model's path -----------

def _analytic_unet(prediction_type):
    """In ``apply_unet``'s place: a smooth ε of the sample, the step and the
    context (so guidance matters), or the v of that ε at the step's noise
    level, v = (ε − σ·x_t) / α, which is what α·ε − σ·x₀ comes to."""
    acp = jnp.asarray(np.cumprod(1.0 - sched_mod.make_betas()), jnp.float32)

    def fake(params, cfg, x, t, context, **kw):
        tone = jnp.tanh(context.mean(axis=(1, 2)))[:, None, None, None]
        eps = jnp.tanh(0.6 * x + tone) * (0.5 + t / 1000.0) + 0.1 * jnp.roll(x, 1, 2)
        if prediction_type == "v_prediction":
            a = acp[t]
            eps = (eps - jnp.sqrt(1.0 - a) * x) / jnp.sqrt(a)
        out = (eps, kw.get("state", ()))
        # apply_unet's arity: the cache is third where a site_plan is given
        return out + (kw.get("attn_cache"),) if "site_plan" in kw else out

    return fake


def _named(pipe, name, prediction_type):
    """A pipeline under a name of its own (a static argument of the jitted
    programs: nothing traced with the real U-Net is served from the cache)."""
    cfg = dataclasses.replace(
        pipe.config, name=name,
        scheduler=dataclasses.replace(pipe.config.scheduler,
                                      prediction_type=prediction_type))
    return dataclasses.replace(pipe, config=cfg)


@pytest.mark.parametrize("scheduler", ["ddim", "plms", "dpm"])
def test_v_model_samples_the_epsilon_models_path(tiny_pipe, monkeypatch, scheduler):
    """``to_epsilon`` sits where every scheduler needs it: through the PLMS
    warm-up (the second evaluation of the first step converts with the
    sample and timestep the network was given), DPM-Solver++'s history of x₀,
    and guidance, a model that returns v made from a known ε lands on the
    images of the model that returns that ε."""
    images = {}
    for kind in ("epsilon", "v_prediction"):
        # the samplers reach the U-Net through models/denoiser.denoise
        monkeypatch.setattr(unet_mod, "apply_unet", _analytic_unet(kind))
        pipe = _named(tiny_pipe, f"analytic-{scheduler}-{kind}", kind)
        out, _, _ = text2image(pipe, ["a cat", "a dog"], None, num_steps=6,
                               scheduler=scheduler, rng=jax.random.PRNGKey(3))
        images[kind] = np.asarray(out).astype(np.int32)
    assert images["epsilon"].std() > 5          # not a flat image
    # float32 rounding of (ε − σx)/α and back: a level of uint8 at most
    assert np.abs(images["epsilon"] - images["v_prediction"]).max() <= 1


def test_v_model_inverts_as_the_epsilon_model_does(tiny_pipe, monkeypatch):
    """Null-text inversion: the DDIM ascent and the inner loop's loss convert
    the guided output before each step, so both models give the same noise
    and the same optimised embeddings."""
    image = (np.random.RandomState(0).rand(64, 64, 3) * 255).astype(np.uint8)
    got = {}
    for kind in ("epsilon", "v_prediction"):
        monkeypatch.setattr(inversion, "apply_unet", _analytic_unet(kind))
        pipe = _named(tiny_pipe, f"analytic-invert-{kind}", kind)
        got[kind] = inversion.invert(pipe, image, "a cat", num_steps=3,
                                     num_inner_steps=2)
    np.testing.assert_allclose(got["v_prediction"].x_t, got["epsilon"].x_t,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got["v_prediction"].uncond_embeddings,
                               got["epsilon"].uncond_embeddings,
                               rtol=1e-3, atol=1e-4)
    with pytest.raises(ValueError, match="phase-gated"):
        inversion.invert(tiny_pipe, image, "a cat", num_steps=3, gate=2)


# -- (c) controller defaults from the model's layout ------------------------

LEVELS = [(SD14, 16), (SD21_BASE, 16), (LDM256, 16), (SD14_HR, 16), (TINY, 16),
          (TINY_LDM, 16), (SD21, 24), (TINY_V, 3)]


@pytest.mark.parametrize("cfg,level", LEVELS, ids=lambda v: getattr(v, "name", str(v)))
def test_controller_defaults_come_from_the_layout(cfg, level, tokenizer):
    """A factory leaves the resolutions nobody gave as ``PaperLevel``s, and
    the layout takes them where the controller meets the model: the paper's
    16² / 16 wherever the pyramid has a 16² level, the level in its place
    where it has none (24 of SD-2.1's 96 / 48 / 24 / 12). The null-text
    variant's 32² stands one level up, and LocalBlend finds its cross sites."""
    layout = unet_layout(cfg.unet)
    assert layout.edit_resolution() == level
    prompts = ["a cat on a mat", "a dog on a mat"]
    kw = dict(tokenizer=tokenizer, max_len=cfg.text.max_length)
    ctrl = factory.attention_replace(prompts, 10, 0.8, 0.4, **kw)
    assert ctrl.edit.self_max_pixels == PaperLevel(0)
    assert layout.resolve(ctrl).edit.self_max_pixels == level ** 2
    refine = factory.attention_refine(prompts, 10, 0.8, 0.4, **kw)
    assert layout.resolve(refine).edit.self_max_pixels == level ** 2
    full = factory.make_controller(prompts, True, 0.8, 0.4, tokenizer, num_steps=10,
                                   equalizer_params={"words": ["dog"], "values": [2.0]})
    assert full.edit.self_max_pixels == PaperLevel(1)
    assert layout.resolve(full).edit.self_max_pixels == (2 * level) ** 2
    blended = factory.make_controller(prompts, True, 0.8, 0.4, tokenizer, num_steps=10,
                                      blend_words=[["cat"], ["dog"]])
    assert blended.blend.resolution == PaperLevel(0)
    if cfg in (TINY, TINY_LDM):
        # As today: the toy presets store up to 8², so the paper's 16 names a
        # level they keep no map of; that is said when the controller meets
        # the model, where it used to surface while tracing.
        with pytest.raises(ValueError, match="resolution 16"):
            layout.resolve(blended)
    else:
        got = layout.resolve(blended)
        assert got.blend.resolution == level
        sites = layout.blend_metas(got.blend.resolution)
        assert sites and all(m.is_cross and m.resolution == level for m in sites)
        assert layout.resolve(got) is got           # nothing left to resolve
    # an explicit value wins, and such a controller comes back as it is
    given = factory.attention_replace(prompts, 10, 0.8, 0.4, self_max_pixels=64, **kw)
    assert given.edit.self_max_pixels == 64 and layout.resolve(given) is given
    assert layout.resolve(None) is None


def test_a_resolution_the_model_lacks_raises_before_any_trace(tokenizer):
    prompts = ["a cat on a mat", "a dog on a mat"]
    layout = unet_layout(SD21.unet)
    blend = factory.local_blend(prompts, [["cat"], ["dog"]], tokenizer, resolution=16)
    with pytest.raises(ValueError, match="resolution 16"):
        layout.resolve(factory.attention_replace(prompts, 10, 0.8, 0.4, tokenizer,
                                                 local_blend=blend))
    # a pyramid with neither a 16² level nor a quarter of its largest side
    odd = dataclasses.replace(TINY.unet, sample_size=20,
                              block_channels=(32, 64), attn_levels=(True, False))
    with pytest.raises(ValueError, match="no default edit resolution"):
        unet_layout(odd).resolve(factory.attention_replace(prompts, 10, 0.8, 0.4,
                                                           tokenizer))
    # a controller that slipped past every entrance does not pick SD-1.4's
    # sites on another pyramid: it cannot be compared at all
    with pytest.raises(TypeError, match="PaperLevel"):
        controller_touches(factory.attention_replace(prompts, 10, 0.8, 0.4, tokenizer),
                           [m for m in layout.metas if not m.is_cross][0])


def test_a_bare_controller_is_resolved_where_it_meets_the_pipeline(tiny_v, monkeypatch):
    """A notebook's ``attention_replace(...)`` with nothing given, straight
    into ``text2image`` and ``sweep``: the program is built with the model's
    own levels (3² on the toy 12 / 6 / 3 latent), not SD-1.4's 16²."""
    _, pipe, _, _ = tiny_v
    prompts = ["a cat on a mat", "a dog on a mat"]
    ctrl = factory.attention_replace(prompts, 4, 0.8, 0.4, pipe.tokenizer,
                                     max_len=pipe.config.text.max_length)
    seen = []
    real = sampler._text2image_jit
    monkeypatch.setattr(sampler, "_text2image_jit",
                        lambda *a, **k: seen.append(a) or real(*a, **k))
    text2image(pipe, prompts, ctrl, num_steps=4)
    met = [x for x in seen[0] if isinstance(x, type(ctrl))]
    assert [c.edit.self_max_pixels for c in met] == [9]

    from p2p_tpu.parallel import sweep

    ctx = jnp.zeros((1, 4, pipe.config.text.max_length, pipe.config.unet.context_dim))
    lat = jnp.zeros((1, 2) + pipe.latent_shape)
    stacked = jax.tree.map(lambda x: x[None], ctrl)
    lowered = sweep(pipe, ctx, lat, stacked, num_steps=4, lower_only=True)
    assert lowered is not None                      # traced with 3², no TypeError


def test_the_cli_and_serve_leave_the_defaults_to_the_model():
    """``p2p-tpu edit --preset sd21`` and ``serve`` give no resolution of
    their own: no new option, 24 / 48² on SD-2.1 once the layout has them,
    and ``serve`` refuses a side the model lacks at admission."""
    from types import SimpleNamespace

    from p2p_tpu import cli
    from p2p_tpu.serve.request import Request, prepare
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    args = cli.build_parser().parse_args(
        ["edit", "--preset", "sd21", "--source", "a cat on a mat",
         "--target", "a dog on a mat", "--blend-words", "mat"])
    assert args.blend_resolution is None
    ctrl = cli._make_controller(args, ["a cat on a mat", "a dog on a mat"],
                                HashWordTokenizer(), 50)
    got = unet_layout(SD21.unet).resolve(ctrl)
    assert (got.blend.resolution, got.edit.self_max_pixels) == (24, 48 * 48)
    got = unet_layout(SD14.unet).resolve(ctrl)
    assert (got.blend.resolution, got.edit.self_max_pixels) == (16, 32 * 32)
    pipe = SimpleNamespace(config=SD21, tokenizer=HashWordTokenizer())
    req = Request(request_id="r", prompt="a cat on a mat", target="a dog on a mat",
                  blend_words="mat")
    assert req.blend_resolution is None
    ready = prepare(req, pipe).controller
    assert (ready.blend.resolution, ready.edit.self_max_pixels) == (24, 48 * 48)
    with pytest.raises(ValueError, match="resolution 16"):
        prepare(dataclasses.replace(req, blend_resolution=16), pipe)


# -- what a launch keeps (tracing) ------------------------------------------

@pytest.mark.parametrize("return_store", [True, False], ids=["store taken back", "no reader"])
def test_a_launch_keeps_each_self_site_and_the_stores_bytes(tiny_v, return_store):
    """The store follows its readers (``AttnLayout.for_readers``): a caller
    that takes it back holds every site under the bound, (12 // 2)²; with no
    reader ``store=True`` keeps nothing, and the 6² self sites above the 3²
    edit window are ``fused_attention``'s like the 12² ones."""
    _, pipe, _, _ = tiny_v
    pipe = _named(pipe, f"tiny-v-launch-{return_store}", "v_prediction")
    ctrl = factory.attention_replace(
        list(PROMPTS), EDIT["num_steps"], EDIT["cross_replace_steps"],
        EDIT["self_replace_steps"], pipe.tokenizer, self_max_pixels=3 * 3,
        max_len=pipe.config.text.max_length, store=True)
    _, _, store = text2image(pipe, list(PROMPTS), ctrl, num_steps=EDIT["num_steps"],
                             rng=jnp.asarray(KEY, jnp.uint32), return_store=return_store)
    launch = launches.programs("jit__text2image_jit")[-1]
    layout = unet_layout(TINY_V.unet)
    selfs = [m for m in layout.metas if not m.is_cross]
    assert sorted(launch.self_sites) == [m.layer_idx for m in selfs]
    for m in selfs:
        site = launch.self_sites[m.layer_idx]
        held = m.pixels <= (36 if return_store else 9)
        assert (site.keys, site.head_dim, site.how, site.geometry) == (
            m.pixels, 16, "edited" if held else "einsum", None)
    assert launch.self_site_counts == (
        {"einsum": 3, "edited": 4} if return_store else {"einsum": 6, "edited": 1})
    # (B, heads, P, K) float32 of every stored site, B = 2 conditional rows
    want = (sum(2 * m.heads * m.pixels * m.key_len * 4 for m in layout.stored_metas())
            if return_store else 0)
    assert launch.store_bytes == want == sum(s.size * 4 for s in store)
    assert (want > 0) == return_store
    assert "controller store %d bytes" % want in launch.describe_sites()
    from p2p_tpu.obs import metrics

    gauge = metrics.registry().get("launch_store_bytes")
    assert gauge.labels(module="jit__text2image_jit").value == want
    # a program without a controller keeps no store
    text2image(_named(pipe, f"tiny-v-plain-{return_store}", "v_prediction"), list(PROMPTS),
               None, num_steps=2, rng=jax.random.PRNGKey(0), return_store=return_store)
    assert launches.programs("jit__text2image_jit")[-1].store_bytes == 0


@pytest.mark.parametrize("return_store", [True, False], ids=["store taken back", "no reader"])
def test_sd21_store_and_kernel_sites_by_the_layout(return_store):
    """What `sd21.edit-replace`'s controller (window 24², ``store=True``) runs,
    from the layout alone. For a caller that takes the store back the bound
    scales to 48², so of 16 self sites the controller holds eleven and five
    are left to ``fused_attention`` at 9,216 keys. The cell takes no store
    back: the five 48² sites are released to the kernel as well."""
    from p2p_tpu.controllers.base import init_store_state
    from p2p_tpu.models import nn
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    whole = unet_layout(SD21.unet)
    assert whole.store_cfg.max_pixels == 48 * 48
    ctrl = factory.attention_replace(
        list(PROMPTS), 50, 0.8, 0.4, HashWordTokenizer(), self_max_pixels=24 * 24,
        max_len=SD21.text.max_length, store=True)
    layout = whole.for_readers(whole.resolve(ctrl), return_store)
    selfs = [m for m in layout.metas if not m.is_cross]
    assert sorted({(m.pixels, m.heads, m.channels // m.heads) for m in selfs}) == [
        (144, 20, 64), (576, 20, 64), (2304, 10, 64), (9216, 5, 64)]
    assert sum(m.store_slot is not None for m in selfs) == (11 if return_store else 0)
    free = [m.pixels for m in selfs if not controller_touches(ctrl, m)]
    assert free == ([9216] * 5 if return_store
                    else [9216, 9216, 2304, 2304, 2304, 2304, 2304, 9216, 9216, 9216])
    assert {nn.flash_block(p, 64, 4) for p in free} == (
        {(512, 3072, 1536)} if return_store else {(512, 3072, 1536), (768, 2304, 1152)})
    # the store's bytes for the two conditional rows: `Launch.store_bytes`
    state = jax.eval_shape(lambda: init_store_state(layout, 2))
    assert sum(s.size * 4 for s in state) == (2_500_323_840 if return_store else 0)
    sched = SchedulerConfig(prediction_type="v_prediction")
    assert SD21.scheduler == sched and SD21.unet.head_dim == 64


@pytest.fixture
def no_compile_cache():
    """JAX leaves metadata out of the cache's key: a cached executable keeps
    the scopes it was compiled with (docs/OBSERVABILITY.md, "Stale scopes")."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_the_v_conversion_sits_under_the_scheduler_step_scope(tiny_v, no_compile_cache):
    """What v-prediction adds to the compiled step is under
    ``sampler/scheduler_step`` and nowhere else, so
    ``sampler.outside_unet_ms_per_step`` stays the whole of the sampler's own
    time and no instruction lands outside the scope vocabulary."""
    import collections

    from p2p_tpu.obs import traceparse

    _, pipe, _, _ = tiny_v
    counts = {}
    for kind in ("v_prediction", "epsilon"):
        text2image(_named(pipe, f"tiny-v-scopes-{kind}", kind), list(PROMPTS), None,
                   num_steps=2, rng=jax.random.PRNGKey(0))
        launch = launches.programs("jit__text2image_jit")[-1]
        hlo = launch.fn.lower(*launch.args, **launch.kwargs).compile().as_text()
        index, _ = traceparse.scope_index(hlo)
        counts[kind] = collections.Counter(
            "/".join(s.split("/")[:2]) if s.startswith("sampler/") else "elsewhere"
            for s in index.values())
    v, eps = counts["v_prediction"], counts["epsilon"]
    assert v["sampler/scheduler_step"] > eps["sampler/scheduler_step"]
    assert v["sampler/cfg"] == eps["sampler/cfg"] and v["elsewhere"] == eps["elsewhere"]
