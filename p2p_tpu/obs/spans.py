"""Nested wall-clock spans with a bounded ring buffer of structured events.

``with span("serve.batch", lanes=4): ...`` records a start and an end event
(name, span id, parent id, nesting depth, timestamp, attributes, duration)
into a fixed-capacity ring buffer — old events are evicted, never buffered
unboundedly — and mirrors the block into a ``jax.profiler.TraceAnnotation``
that carries the same ``span`` and ``parent`` ids, so a ring event and the
region on the host rows of an xplane/Perfetto trace are one record by id
(docs/OBSERVABILITY.md shows how to line the two up). Span durations are
additionally observed into the ``span_duration_ms`` histogram of the default
metrics registry, so the Prometheus snapshot carries the per-span-name
distribution even after the ring has evicted the events.

Host-side only: entering a span never traces anything into an XLA program
(``TraceAnnotation`` is a profiler marker, not an op), so the
telemetry-disabled jaxpr-identity guarantee is unaffected by spans entirely.
``set_enabled(False)`` turns :func:`span` into a pure pass-through for
callers who want zero event traffic.

Timestamps are ``t_ns = time.monotonic_ns()``: the clock of the compile
ledger (``utils.cache``), of the benchmark's harness and of anything else in
the process that reads ``time.monotonic()``, so spans need no private epoch
to be compared with them.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import time
from collections import deque
from typing import List, Optional

from . import metrics as metrics_mod

DEFAULT_CAPACITY = 4096

class SpanRecorder:
    """Bounded event sink. ``dropped`` counts ring-evicted events so an
    export can say it is a suffix, not the whole run."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self.total = 0

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    @property
    def dropped(self) -> int:
        return self.total - len(self._ring)

    def resize(self, capacity: int) -> None:
        """Change the ring capacity in place, keeping the most recent
        events. ``total`` is preserved, so the ``dropped`` count stays
        honest across a resize: shrinking evicts (and counts) the oldest
        events exactly as organic eviction would."""
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        self._ring = deque(self._ring, maxlen=capacity)

    def emit(self, event: dict) -> None:
        self._ring.append(event)
        self.total += 1

    def events(self) -> List[dict]:
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.total = 0


_recorder = SpanRecorder()
_stack: List[int] = []           # active span ids, innermost last
_attached: List[dict] = []       # attach() contexts, innermost last
_ids = itertools.count(1)
_enabled = True


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = bool(flag)


def set_capacity(capacity: int) -> None:
    """Resize the process ring (``serve --events-ring`` /
    ``P2P_OBS_EVENTS_RING``). Two-pool serving roughly doubles event
    volume over the single-pool engine, and a too-small ring silently
    evicts mid-trace — the meta line's ``dropped`` count stays honest
    across any resize (see :meth:`SpanRecorder.resize`)."""
    _recorder.resize(capacity)


def capacity() -> int:
    return _recorder.capacity


@contextlib.contextmanager
def attach(**attrs):
    """Attach context attributes (request identity, trace ids) to every
    span opened inside the block — how the flight-tracing layer stamps
    dispatch spans with the requests they carry without every call site
    threading ids by hand. Nested attaches merge, innermost winning; the
    attributes ride both the start and end events."""
    _attached.append(attrs)
    try:
        yield
    finally:
        _attached.pop()


def _attached_attrs() -> dict:
    out: dict = {}
    for d in _attached:
        out.update(d)
    return out


def recorder() -> SpanRecorder:
    return _recorder


def events() -> List[dict]:
    return _recorder.events()


def clear() -> None:
    _recorder.clear()


def _trace_annotation(name: str, sid: int, parent: Optional[int]):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` that carries the
    span's ids (the profiler shows them as the event's ``span`` / ``parent``
    arguments; a root's parent is 0), or None when jax (or its profiler) is
    unavailable — spans must not *require* jax."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, span=sid,
                                            parent=parent or 0)
    except Exception:
        return None


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record a nested wall-clock span around the block.

    ``attrs`` must be JSON-serializable scalars (lane counts, step counts,
    cache-hit flags); they ride both the start and end events."""
    if not _enabled:
        yield None
        return
    sid = next(_ids)
    parent = _stack[-1] if _stack else None
    depth = len(_stack)
    if _attached:
        attrs = {**_attached_attrs(), **attrs}
    t0 = time.monotonic_ns()
    _recorder.emit({"event": "span_start", "span": sid, "name": name,
                    "parent": parent, "depth": depth, "t_ns": t0, **attrs})
    _stack.append(sid)
    ann = _trace_annotation(name, sid, parent)
    if ann is not None:
        ann.__enter__()
    try:
        yield sid
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        _stack.pop()
        t1 = time.monotonic_ns()
        dur_ms = (t1 - t0) / 1e6
        _recorder.emit({"event": "span_end", "span": sid, "name": name,
                        "parent": parent, "depth": depth, "t_ns": t1,
                        "dur_ms": dur_ms, **attrs})
        metrics_mod.registry().histogram(
            "span_duration_ms", "wall-clock span durations by span name",
            labels=("name",),
            buckets=metrics_mod.LATENCY_MS_BUCKETS,
        ).labels(name=name).observe(dur_ms)


def completed(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Record a block that has already ended, timed by the caller on
    ``time.monotonic_ns()``'s clock, as one ``span_end`` event with no
    parent: the innermost open span is kept as ``within``, so that the block
    comes off no span's self time. For a caller that cannot wrap the block
    in :func:`span`: the collector watch (``obs.collector``) runs inside a
    collection, which can start in any allocation, so this takes no lock,
    feeds no histogram, and mirrors nothing into the profiler."""
    if not _enabled:
        return
    _recorder.emit({"event": "span_end", "span": next(_ids), "name": name,
                    "parent": None, "within": _stack[-1] if _stack else None,
                    "depth": len(_stack), "t_ns": t1_ns,
                    "dur_ms": (t1_ns - t0_ns) / 1e6, **attrs})


def write_jsonl(fp) -> int:
    """Dump the ring buffer as JSONL to an open file; returns lines written.
    A leading meta line records capacity/total/dropped so consumers know
    whether the log is complete."""
    fp.write(json.dumps({"event": "meta", "total": _recorder.total,
                         "dropped": _recorder.dropped}) + "\n")
    n = 1
    for ev in _recorder.events():
        fp.write(json.dumps(ev) + "\n")
        n += 1
    return n


def active_depth() -> int:
    return len(_stack)


def active_span() -> Optional[int]:
    return _stack[-1] if _stack else None
