"""Host-side clocks of a run: compile events from ``jax.monitoring`` and the
benchmark's own spans."""

from __future__ import annotations

import contextlib
import time


class CompileClock:
    """Every backend compile that ``jax.monitoring`` reports (a read from the
    persistent cache counts as one), with the host time at which it ended
    (the idea of ``chip_smoke.py:CompileClock``, kept here so that the program
    cannot move it)."""

    def __init__(self):
        import jax

        self.backend = []            # (ended_at, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend.append((time.monotonic(), secs))

    def backend_seconds(self, before: float) -> float:
        return sum(s for t, s in self.backend if t <= before)

    def compiles_between(self, t0: float, t1: float, at_least: float = 1.0) -> int:
        return sum(1 for t, s in self.backend if t0 < t <= t1 and s >= at_least)


class Spans:
    """``with spans("encode", call=3): ...`` records (name, start, end, call)
    on the host clock and writes the same span into the profiler's trace."""

    def __init__(self):
        self.rows = []

    @contextlib.contextmanager
    def __call__(self, name: str, call: int = -1):
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.monotonic(), call))
