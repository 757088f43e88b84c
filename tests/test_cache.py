"""Unit tests for the persistent-compile-cache helper (`p2p_tpu/utils/cache.py`).

Two rules: with ``JAX_COMPILATION_CACHE_DIR`` set, that directory and no
other ``jax_compilation_cache_dir`` update; unset, exactly
``<checkout>/.jax_cache`` whatever ``XLA_FLAGS`` says."""

import os

import jax
import pytest

from p2p_tpu.utils import cache as cache_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def dir_updates(monkeypatch):
    """Every ``jax.config.update("jax_compilation_cache_dir", X)`` made while
    the test runs, recorded and not applied (the suite's own cache config
    stays as conftest established it)."""
    seen = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return seen


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path,
                                                 dir_updates):
    d = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setenv("XLA_FLAGS", "--xla_tpu_scoped_vmem_limit_kib=131072")
    assert cache_mod.default_cache_dir() == d
    assert cache_mod.enable_persistent_cache() == d
    # JAX reads the variable itself: no update at all, so none to another
    # directory — and nothing is created on its behalf.
    assert dir_updates == []
    assert not os.path.exists(d)


@pytest.mark.parametrize("xla_flags", [
    None, "--xla_tpu_scoped_vmem_limit_kib=131072",
    "--xla_force_host_platform_device_count=8"])
def test_default_dir_is_the_checkouts_whatever_xla_flags(monkeypatch,
                                                         dir_updates,
                                                         xla_flags):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if xla_flags is None:
        monkeypatch.delenv("XLA_FLAGS", raising=False)
    else:
        monkeypatch.setenv("XLA_FLAGS", xla_flags)
    want = os.path.join(REPO, ".jax_cache")
    assert cache_mod.default_cache_dir() == want
    assert cache_mod.enable_persistent_cache() == want
    assert cache_mod.enable_persistent_cache() == want     # idempotent
    assert dir_updates == [want, want]
    assert os.path.isdir(want)


def test_setup_failure_raises(monkeypatch, tmp_path, dir_updates):
    # A cache directory that cannot be created is an error, not a warning:
    # the default dir is pointed below a regular file.
    blocker = tmp_path / "file"
    blocker.write_text("")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache_mod, "_DEFAULT_DIR", str(blocker / "cache"))
    with pytest.raises(OSError):
        cache_mod.enable_persistent_cache()
    assert dir_updates == []
