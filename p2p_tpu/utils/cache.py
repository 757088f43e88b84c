"""Persistent XLA compile cache shared by every entry point.

The SD-1.4 sampling program takes minutes of XLA compilation. With a
persistent cache, bench.py / the CLI / the profiling tools compile each
distinct program once per checkout and reload it afterwards (works for both
the CPU and TPU backends; keyed on HLO + compile options + backend).

One rule, one resolver (:func:`default_cache_dir`): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps its cache there and
this module sets nothing; otherwise the single fixed ``<checkout>/.jax_cache``
(gitignored). The path is part of the cache's key, so it never depends on
``XLA_FLAGS`` (JAX already keys entries on the flags), the pid, the time or
a temp name. tests/conftest.py and the tools that start children export the
resolved directory before ``import jax`` so parent and child agree.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def default_cache_dir() -> str:
    """The cache directory every entry point agrees on: a pre-set
    ``JAX_COMPILATION_CACHE_DIR`` verbatim, else ``<checkout>/.jax_cache``.
    jax-free, so callers can resolve it before their first ``import jax``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def enable_persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`default_cache_dir` and return the directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read it, and nothing
    is configured here. Safe to call more than once; a directory that
    cannot be created raises (an entry point that silently recompiles
    minutes of XLA per start is not "working")."""
    cache_dir = default_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
