"""Progress + profiling hooks — the observability layer.

The reference shows a tqdm bar over timesteps (`/root/reference/ptp_utils.py:21,167`)
and a manually-ticked bar over null-text inner iterations
(`/root/reference/null_text.py:578,596-600`). Inside a jitted ``lax.scan``
there is no Python loop to hang a bar on, so progress is reported from the
compiled program via ``jax.debug.callback``: the scan body emits its step
index, and a host-side reporter turns the stream into a single rewriting
line with measured ms/step. The callback is async (no device sync); when
``progress=False`` nothing is traced in, so the silent path's XLA program is
unchanged.

``trace(logdir)`` wraps a block in a ``jax.profiler`` trace — the TPU-native
answer to SURVEY §5's "tracing: none". The resulting directory contains an
xplane + chrome-trace (``*.trace.json.gz``) viewable in Perfetto/TensorBoard.

The same callback channel doubles as the telemetry subsystem's host-event
path (docs/OBSERVABILITY.md): ``emit_step`` carries an optional static
``phase`` tag and ``emit_event`` carries arbitrary traced scalars, both
fanned out to an installable obs sink (``set_obs_sink`` — installed by
``p2p_tpu.obs.device.instrument``) alongside the progress reporter. The
one discipline everything here shares: with ``enabled=False`` *nothing* is
traced into the program — the compiled XLA is bit-identical to a build
that never imported this module.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from typing import Optional

import jax


class StepReporter:
    """Host-side sink for step-index callbacks from a compiled loop.

    Async callbacks can arrive out of order; the reporter tracks the highest
    step seen and smoothed step time. Writes a single rewriting line to
    stderr (a terminal-friendly stand-in for tqdm)."""

    def __init__(self, total: int, label: str = "sampling", stream=None):
        self.total = int(total)
        self.label = label
        self.stream = stream or sys.stderr
        self._last_step = -1
        self._last_t = None
        self._ema_ms = None

    def __call__(self, step) -> None:
        step = int(step)
        now = time.perf_counter()
        if step <= self._last_step:
            return
        if self._last_t is not None and step > 0:
            dt_ms = (now - self._last_t) / max(1, step - self._last_step) * 1000
            self._ema_ms = (dt_ms if self._ema_ms is None
                            else 0.7 * self._ema_ms + 0.3 * dt_ms)
        self._last_step = step
        self._last_t = now
        rate = f" {self._ema_ms:6.1f} ms/step" if self._ema_ms else ""
        self.stream.write(f"\r{self.label}: step {step + 1}/{self.total}{rate}")
        self.stream.flush()
        if step + 1 >= self.total:
            self.stream.write("\n")


# The compiled program must not bake a particular reporter instance in (the
# jit cache outlives any one call), so the traced callback targets this
# module-level slot; callers install their reporter just before launching.
_active: Optional[StepReporter] = None


def set_active(reporter: Optional[StepReporter]) -> None:
    global _active
    _active = reporter


def activate(total: int, label: str = "sampling") -> None:
    """Install a fresh reporter for a progress-enabled launch, first
    draining any still-in-flight callbacks from a previous progress run
    (dispatch is async) so late steps can't poison the new reporter's
    monotonic step filter. The one place the drain-then-install discipline
    lives — used by ``text2image``, ``invert`` phases, and ``sweep``."""
    jax.effects_barrier()
    set_active(StepReporter(int(total), label))


# Secondary sink alongside the rewriting-line reporter: the serve engine
# installs a per-batch hook here to turn the same compiled-loop callback
# stream into per-request step progress records (engine_loop.run_entries),
# without disturbing whatever reporter is active.
_step_hook = None


def set_step_hook(fn) -> None:
    """Install (or clear, with ``None``) a callable invoked with every step
    index the compiled loop emits, in addition to the active reporter."""
    global _step_hook
    _step_hook = fn


# Third sink: the telemetry collector (p2p_tpu.obs.device.StepCollector),
# called as sink("step", step_index, phase) for step callbacks and
# sink(tag, value, None) for generic emit_event events. Installed only for
# the duration of an instrumented run — None costs one load + is-None test.
_obs_sink = None


def set_obs_sink(fn) -> None:
    """Install (or clear, with ``None``) the telemetry sink receiving every
    step/event callback the compiled loops emit."""
    global _obs_sink
    _obs_sink = fn


# Fourth sink: the serve watchdog's heartbeat (p2p_tpu.serve.faults).
# Called with no arguments on every step callback, regardless of the
# report flag — a compiled loop still emitting steps is alive, however
# slow, so the dispatch-time watchdog re-arms instead of shooting it; a
# hung compile/execute emits nothing and the deadline stands.
_watchdog_sink = None


def set_watchdog_sink(fn) -> None:
    """Install (or clear, with ``None``) a zero-arg callable invoked on
    every step callback — the serve watchdog's liveness heartbeat."""
    global _watchdog_sink
    _watchdog_sink = fn


def _dispatch(step, phase=None, report=True) -> None:
    # report=False: a metrics-only emission — the progress surfaces
    # (rewriting-line reporter, serve step hook) must stay silent. Nothing
    # clears _active between runs (dispatch is async; there is no reliable
    # "last callback delivered" moment), so a stale reporter from an
    # earlier progress run would otherwise write garbled lines during a
    # later quiet-but-instrumented run.
    if report:
        r = _active
        if r is not None:
            r(step)
        h = _step_hook
        if h is not None:
            h(step)
    s = _obs_sink
    if s is not None:
        s("step", int(step), phase)
    w = _watchdog_sink
    if w is not None:
        w()


def emit_step(enabled: bool, step, phase: Optional[str] = None,
              report: bool = True) -> None:
    """Trace-time: emit ``step`` to the active reporter (and the obs sink)
    from inside a jitted loop. ``phase`` is a *static* tag naming which scan
    emitted the step ('phase1'/'phase2' for the gated sampler, 'invert'/
    'null_text' for inversion) — it is baked into the host callback, never
    traced. ``report=False`` (metrics-only emission: telemetry on, progress
    off) bypasses the reporter/step-hook surfaces and feeds only the obs
    sink. With ``enabled=False`` nothing is traced in — the compiled
    program is identical to the silent one."""
    if enabled:
        cb = (_dispatch if (phase is None and report)
              else functools.partial(_dispatch, phase=phase, report=report))
        jax.debug.callback(cb, step, ordered=False)


def _dispatch_event(tag, value) -> None:
    s = _obs_sink
    if s is not None:
        s(tag, value, None)


def emit_event(enabled: bool, tag: str, value) -> None:
    """Trace-time: emit a generic ``(tag, value)`` host event from inside a
    jitted program — ``tag`` static, ``value`` traced (e.g. the null-text
    inner-iteration count). Same contract as ``emit_step``: disabled means
    nothing is traced in."""
    if enabled:
        jax.debug.callback(functools.partial(_dispatch_event, tag), value,
                           ordered=False)


@contextlib.contextmanager
def trace(logdir: Optional[str]):
    """``with trace("/tmp/p2p_trace"): ...`` — jax.profiler trace of the
    block; no-op when ``logdir`` is falsy. Only the process that holds the
    chip can trace it."""
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
