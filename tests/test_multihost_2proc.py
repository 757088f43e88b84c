"""Real 2-process `jax.distributed` smoke test on localhost (CPU backend).

`multihost.initialize` had only been exercised in its
single-process degradation. Here two actual OS processes join through a
localhost coordinator (gloo CPU collectives), build the `global_mesh`, and
run a tiny dp edit-group sweep whose group axis spans both processes — the
DCN-facing launch path (`p2p_tpu/parallel/multihost.py:29-108`) end to end.

Each worker gets 2 virtual CPU devices → a global (dp=4, tp=1) mesh. The
workload is the TINY-config sweep (2 steps) so the two concurrent XLA
compiles stay cheap on the single-core build host.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    from p2p_tpu.utils.cache import enable_persistent_cache
    enable_persistent_cache()
    from p2p_tpu.parallel import multihost
    import jax, jax.numpy as jnp

    assert multihost.initialize(), "distributed init did not activate"
    assert jax.process_count() == 2
    mesh = multihost.global_mesh(tp=1)
    assert dict(mesh.shape) == {{"dp": 4, "tp": 1}}, dict(mesh.shape)

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import Pipeline, encode_prompts
    from p2p_tpu.models import TINY, init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.parallel import seed_latents, sweep
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    def barrier(name):
        # Rendezvous through the coordination service, NOT a gloo
        # collective: on the single-core build host the workers' compiles
        # serialize and skew by minutes, while gloo's context handshake
        # times out at a fixed ~30s. The coordination barrier takes a real
        # timeout, so the first gloo op on each clique then happens with
        # millisecond skew.
        from jax._src import distributed
        distributed.global_state.client.wait_at_barrier(name, 600_000)

    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok)
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    g = 4
    ctrl = factory.attention_replace(
        prompts, 2, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=8 * 8, max_len=cfg.text.max_length)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (g,) + x.shape), ctrl)
    cond = encode_prompts(pipe, prompts)
    uncond = encode_prompts(pipe, [""] * len(prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)
    ctx = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(3), g, len(prompts),
                        pipe.latent_shape)
    barrier("pre-sweep")  # first gloo ops (sweep's device_puts) follow
    imgs, _ = sweep(pipe, ctx, lats, ctrls, num_steps=2, mesh=mesh)
    assert imgs.shape == (g, len(prompts), cfg.image_size, cfg.image_size, 3)
    # The group axis is genuinely sharded: this process holds 2 of 4 groups
    # (one per local device), and owns the matching host-side slice.
    assert len(imgs.addressable_shards) == 2
    own = list(multihost.process_groups(g))
    assert own == ([0, 1] if jax.process_index() == 0 else [2, 3]), own
    # Explicit sync before exit: without it the faster worker exits minutes
    # early and the 30s distributed-shutdown barrier times out.
    barrier("workers-done")
    print("MH-WORKER-OK", flush=True)
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_pair(script, port):
    def launch(pid):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + ["--xla_force_host_platform_device_count=2"])
        return subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    procs = [launch(0), launch(1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    problems = [f"worker {pid} rc={p.returncode}:\n{out[-3000:]}"
                for pid, (p, out) in enumerate(zip(procs, outs))
                if p.returncode != 0 or "MH-WORKER-OK" not in out]
    return problems


#: The baked jaxlib's CPU client refuses cross-process SPMD outright —
#: executing (or staging toward) any computation whose sharding spans
#: processes raises exactly this. Root-caused during ISSUE 6 triage: the
#: staging half (device_put of an unsharded value running a cross-host
#: assert_equal collective) is fixed in-repo
#: (`parallel.sweep._stage_sharded` donates per-process shards with no
#: collective), but the jitted sweep execution itself still needs
#: multiprocess CPU SPMD, which this toolchain removed. Environment
#: drift, not a repo regression — the xfail below keys on this exact
#: message so the test resurrects itself the day the toolchain regains
#: CPU multiprocess execution (any OTHER failure still fails loudly).
_CPU_MULTIPROCESS_UNSUPPORTED = (
    "Multiprocess computations aren't implemented on the CPU backend")


def test_single_process_virtual_mesh_dp_sweep():
    """The 2-process worker's exact sweep, single-process on a virtual
    dp=4 mesh — so the mesh staging/dispatch path (`sweep(mesh=...)`:
    `_stage_sharded` device donation, the sharded `_sweep_jit` execution,
    `process_groups` ownership arithmetic) runs in tier-1 on EVERY suite
    run. The 2-proc test below is slow-marked AND xfailed on the baked
    jaxlib's missing CPU multiprocess SPMD, which used to leave mesh
    execution with zero always-on coverage; this lane is the same
    workload minus the process boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device CPU platform")

    from p2p_tpu.controllers import factory
    from p2p_tpu.engine.sampler import Pipeline, encode_prompts
    from p2p_tpu.models import TINY, init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.parallel import (make_mesh, process_groups, seed_latents,
                                  sweep)
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    cfg = TINY
    tok = HashWordTokenizer(model_max_length=cfg.text.max_length)
    pipe = Pipeline(
        config=cfg,
        unet_params=init_unet(jax.random.PRNGKey(0), cfg.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), cfg.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), cfg.vae),
        tokenizer=tok)
    prompts = ["a cat riding a bike", "a dog riding a bike"]
    g = 4
    mesh = make_mesh(g, tp=1)
    ctrl = factory.attention_replace(
        prompts, 2, cross_replace_steps=0.8, self_replace_steps=0.4,
        tokenizer=tok, self_max_pixels=8 * 8, max_len=cfg.text.max_length)
    ctrls = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (g,) + x.shape), ctrl)
    cond = encode_prompts(pipe, prompts)
    uncond = encode_prompts(pipe, [""] * len(prompts))
    ctx = jnp.concatenate([uncond, cond], axis=0)
    ctx = jnp.broadcast_to(ctx[None], (g,) + ctx.shape)
    lats = seed_latents(jax.random.PRNGKey(3), g, len(prompts),
                        pipe.latent_shape)
    imgs, _ = sweep(pipe, ctx, lats, ctrls, num_steps=2, mesh=mesh)
    assert imgs.shape == (g, len(prompts), cfg.image_size, cfg.image_size,
                          3)
    # The group axis is genuinely sharded: one whole group per device,
    # and single-process ownership is the full group list.
    assert len(imgs.addressable_shards) == g
    assert {s.data.shape[0] for s in imgs.addressable_shards} == {1}
    assert list(process_groups(g)) == [0, 1, 2, 3]
    # Same math as the mesh-less engine, at the documented vmap tolerance.
    want, _ = sweep(pipe, ctx, lats, ctrls, num_steps=2, mesh=None)
    np.testing.assert_allclose(np.asarray(imgs, np.float32),
                               np.asarray(want, np.float32), atol=1.0)


@pytest.mark.slow
def test_two_process_dp_sweep(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=repo))

    problems = _run_pair(script, _free_port())
    if problems and not any(_CPU_MULTIPROCESS_UNSUPPORTED in p
                            for p in problems):
        # Distributed-runtime startup (coordinator connect, gloo rendezvous)
        # can flake under a loaded single-core host; one clean retry on a
        # fresh port distinguishes a flake from a real regression.
        problems = _run_pair(script, _free_port())
    if any(_CPU_MULTIPROCESS_UNSUPPORTED in p for p in problems):
        pytest.xfail(
            "jaxlib CPU client cannot execute multiprocess SPMD "
            f"({_CPU_MULTIPROCESS_UNSUPPORTED!r}) — toolchain drift "
            "documented above; the multihost launch path is exercised up "
            "to execution (init, mesh build, collective-free staging)")
    assert not problems, "\n---\n".join(problems)
