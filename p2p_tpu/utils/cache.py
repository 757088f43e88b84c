"""Persistent XLA compile cache shared by every entry point.

The SD-1.4 sampling program takes minutes of XLA compilation. With a
persistent cache, the CLI, the tools and the benchmark compile each
distinct program once per checkout and reload it afterwards (works for both
the CPU and TPU backends; keyed on HLO + compile options + backend).

One rule, one resolver (:func:`default_cache_dir`): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps its cache there and
this module sets nothing; otherwise the single fixed ``<checkout>/.jax_cache``
(gitignored). The path is part of the cache's key, so it never depends on
``XLA_FLAGS`` (JAX already keys entries on the flags), the pid, the time or
a temp name. tests/conftest.py and the tools that start children export the
resolved directory before ``import jax`` so parent and child agree.

:func:`enable_persistent_cache` also starts the program's compile ledger
(:class:`CompileLedger`): one ``jax.monitoring`` listener that writes a row
for every jaxpr trace, lowering, backend compile and persistent-cache read,
on ``time.monotonic()`` — the clock of ``obs.spans`` and of the benchmark's
harness. It answers what no counter did: how long set-up traces and lowers
before each cache read, how a read splits into the key and the read itself,
and which programs are compiled in every process because JAX never caches
them. Started with it, the collector watch (``obs.collector``) times every
collection of Python's cyclic garbage collector on the same clock.
``CompileLedger.started_at`` is when both began: the first instant the
program is in charge of the process.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def default_cache_dir() -> str:
    """The cache directory every entry point agrees on: a pre-set
    ``JAX_COMPILATION_CACHE_DIR`` verbatim, else ``<checkout>/.jax_cache``.
    jax-free, so callers can resolve it before their first ``import jax``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


class CompileRow(NamedTuple):
    kind: str          # one of CompileLedger.KINDS
    seconds: float
    ended_at: float    # time.monotonic()
    name: str          # the function or module JAX names in the event


class CompileLedger:
    """Bounded rows of what JAX spent on building programs, from
    ``jax.monitoring`` (which fires on compile events only, never per call):

    - ``trace``: tracing a function to a jaxpr; ``lower``: jaxpr to MLIR;
    - ``backend``: a backend compile that really compiled;
    - ``cache_hit``: a "backend compile" that was a read of the persistent
      cache (JAX times both under one event), ``seconds`` the whole of it:
      the cache key (serializing and hashing the module), the read, and
      JAX's bookkeeping around them;
    - ``cache_read``: the read inside a ``cache_hit``, as JAX times it
      (``/jax/compilation_cache/cache_retrieval_time_sec``: the entry read,
      decompressed and loaded as an executable). It is written just ahead
      of its hit, under the hit's name, and lies inside it;
    - ``cache_miss``: the cache was asked and had no entry; ``seconds`` is the
      compile that followed, which also has its ``backend`` row. A ``backend``
      row without one was never offered to the cache.

    A ``backend`` row's ``name`` says which program compiled although the
    cache is on. ``started_at`` is the ``time.monotonic()`` at which the
    ledger was made, and :func:`compile_ledger` starts listening at once.

    Every row also feeds ``compiles_total`` / ``compile_ms`` of
    ``obs.device.record_compile`` under ``what`` = the row's kind."""

    CAPACITY = 1 << 16
    KINDS = ("trace", "lower", "backend", "cache_hit", "cache_miss",
             "cache_read")
    _DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration": "backend",
                  "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read"}

    def __init__(self):
        self.started_at = time.monotonic()
        self._rows: deque = deque(maxlen=self.CAPACITY)
        self._pending = threading.local()   # cache events of the open compile
        #: Programs built so far, compiled or read: ``obs.launches`` compares
        #: it around a call to see a first launch.
        self.programs = 0

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self._pending.asked = True
        elif event == "/jax/compilation_cache/cache_hits":
            self._pending.hit = True

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = self._DURATIONS.get(event)
        if kind is None:
            return
        if kind == "cache_read":    # the backend event that closes it names it
            self._pending.read = (float(seconds), time.monotonic())
            return
        name, now = str(kw.get("fun_name", "")), time.monotonic()
        if kind == "backend":
            pending = self._pending.__dict__
            asked = pending.pop("asked", False)
            read = pending.pop("read", None)
            if pending.pop("hit", False):
                kind = "cache_hit"
                if read is not None:
                    self._append("cache_read", read[0], read[1], name)
            elif asked:
                self._append("cache_miss", seconds, now, name)
            self.programs += 1
        self._append(kind, seconds, now, name)

    def _append(self, kind: str, seconds: float, ended_at: float,
                name: str) -> None:
        from ..obs.device import record_compile

        self._rows.append(CompileRow(kind, float(seconds), ended_at, name))
        record_compile(seconds * 1e3, what=kind)

    def rows(self, *kinds: str, since: float = float("-inf"),
             before: float = float("inf")) -> List[CompileRow]:
        """Rows of ``kinds`` (all if none given) that ended in
        ``(since, before]`` on ``time.monotonic()``."""
        return [r for r in list(self._rows)
                if (not kinds or r.kind in kinds)
                and since < r.ended_at <= before]


_ledger: Optional[CompileLedger] = None


def compile_ledger() -> CompileLedger:
    """The process's ledger, listening from the first call on; the collector
    watch (``obs.collector``) starts with it."""
    global _ledger
    if _ledger is None:
        import jax

        from ..obs import collector

        _ledger = CompileLedger()
        jax.monitoring.register_event_listener(_ledger._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _ledger._on_duration)
        collector.start()
    return _ledger


def enable_persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`default_cache_dir`, start the compile ledger and the collector
    watch, and return the directory. With ``JAX_COMPILATION_CACHE_DIR`` set
    JAX has already read it, and no directory is configured here. Safe to
    call more than once; a
    directory that cannot be created raises (an entry point that silently
    recompiles minutes of XLA per start is not "working")."""
    compile_ledger()
    cache_dir = default_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
