"""Unified telemetry: metrics registry, span tracing, device instrumentation.

Dependency-free modules every other subsystem reports through (see
docs/OBSERVABILITY.md for the metric catalog and span taxonomy):

- :mod:`.metrics` — process-global registry of counters, gauges and
  fixed-bucket histograms with labeled families, snapshot/reset semantics,
  Prometheus text exposition and JSONL export.
- :mod:`.spans` — nested wall-clock spans on ``time.monotonic_ns()`` in a
  bounded (configurable) ring buffer, mirrored into
  ``jax.profiler.TraceAnnotation`` under the same span ids so a ring event
  and its trace event are one record; ``attach()`` stamps spans with
  request identity.
- :mod:`.flight` — request-scoped flight tracing for the serve engine:
  per-request stage timelines across the two program pools (stitched
  across crash-replay), a Chrome-trace/Perfetto export, and the blackbox
  post-mortem recorder.
- :mod:`.device` — the host half of the compiled-loop callback channel
  (``utils.progress.emit_step``/``emit_event``): per-phase step timing,
  compile-time recording, per-device ``memory_stats()`` gauges. Imported
  explicitly (``from p2p_tpu.obs import device``) because it pulls jax;
  this package root stays jax-free so CLI parsing and the serve data
  structures can import metrics/spans without a backend.
- :mod:`.costmodel` — the cost observatory (ISSUE 14): XLA cost cards
  (``cost_analysis``/``memory_analysis``), the per-platform peak table
  (datasheet on chip, calibrated microbenchmarks on a CPU rehearsal
  host), roofline/MFU arithmetic, the frozen canonical budgets behind
  the ``cost_regression`` gate, and the serve engine's ``CostScope``
  hook. Imported explicitly for the same jax-at-import reason as
  ``device`` (jax only inside functions, but its consumers are all
  jax-side).
- :mod:`.traceparse` — shared chrome-trace / WorkloadProfile parsing
  (ISSUE 18): the ``perfscope --sites`` named_scope fold, the HLO
  instruction→scope index (``scope_index``; ``op_site_index`` is its
  attention-site form) that recovers measured per-scope shares from
  traces that name instructions only, and the ledger format helpers.
  Stdlib-only; safe from tools.
- :mod:`.launches` — which compiled programs the entry points launched,
  kept abstract (shapes and shardings, never arrays), and the scope index
  of each, built from the compiled program's text when somebody asks.
  Imported explicitly; jax only inside functions.
- :mod:`.prodscope` — in-engine sampled device profiling (ISSUE 18):
  the deterministic sampling plan, the bounded on-disk trace ring, the
  mergeable WorkloadProfile ledger and the EWMA drift sentinels behind
  ``serve --profile``. Imported explicitly (``from p2p_tpu.obs import
  prodscope``) — module import is jax-free, but capture methods pull
  jax, and its only consumer is the serve engine.
- :mod:`.collector` — the collector watch: every collection of the cyclic
  garbage collector on ``time.monotonic()``, started once per process with
  the compile ledger (``utils.cache.compile_ledger``). Imported
  explicitly; jax only inside functions.

The TPU-native discipline: disabling telemetry traces *nothing* into any
XLA program (the ``emit_step(enabled=False)`` contract, pinned by jaxpr
identity tests), and everything here is host-side — enabling it changes
wall-clock overhead only, never numerics.
"""

from . import flight, metrics, spans  # noqa: F401  (device is explicit)
from .metrics import registry  # noqa: F401
from .spans import span  # noqa: F401

__all__ = ["flight", "metrics", "spans", "registry", "span"]
