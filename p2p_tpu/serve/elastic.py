"""Elastic mesh serving: pressure-driven dp resize decisions (ISSUE 19).

``serve --elastic`` lets the engine change its own mesh width while
serving. The frozen-topology engine (PR 9) answers load swings only with
the degradation ladder — shed and shrink — which can never *grow*
capacity and wastes healthy chips on the way down. This module is the
ladder run in both directions: an :class:`ElasticController` watches the
same windowed queue-pressure signal ``DegradeConfig`` watches, with
separate sustain windows for scale-up and scale-down plus a cooldown, so
the two directions cannot flap against each other.

The controller only *decides*; the engine executes the journaled resize
protocol at a batch boundary (docs/SERVING.md "Elastic meshes"):

1. pick the target dp — the next power of two up or down, clamped to
   ``[min_dp, max_dp]`` where ``max_dp`` defaults to what the process
   actually has (a decision can never exceed local devices);
2. **prewarm** the target topology's programs out-of-band — compile-ahead
   on the target ``mesh_key`` buckets while the old mesh keeps serving,
   never an in-band compile after cutover;
3. park in-flight phase-1 hand-offs via the spill path (the PR-12
   preemption machinery), journal a ``resize`` event (old/new topology +
   parked ids), fsync;
4. swap the engine's mesh/runner-factory/bucket tables and resume the
   parked carries restaged onto the new shards (``stack_carries(mesh=)``).

Everything between the durable ``resize`` record and cutover completion
is a crash window the ``kill_during_resize`` chaos kind drills: a restart
folds the record's ``new_dp`` out of the WAL (``ReplayState.mesh_dp``)
and comes back *on the target topology*, replaying parked work
exactly-once. The ``resize`` event is declared in
``p2p_tpu.analysis.protocol.DECLARED_EVENTS`` and the restart-on-target
fold is the ``resize-target-restart`` invariant the walcheck pass
(ISSUE 20) machine-checks with a crash injected at every record boundary
around the event — the chaos kind samples the window, the model check
exhausts it.

SLO awareness: a scale-down is deferred while premium-tier work is
waiting (queued or parked) — shrinking under a premium backlog would put
the highest tier behind a cutover pause it never caused. Scale-ups are
never deferred.

Decision thresholds scale with the current width: pressure is judged
per-device (``depth > up_depth · dp`` sustained for ``up_window_ms`` ⇒
grow; ``depth < down_depth · dp`` sustained for ``down_window_ms`` ⇒
shrink), so a mesh twice as wide needs twice the backlog to grow again —
the same per-device-meaning discipline as ``--max-batch``.

Like every serve sidecar, off means off: ``elastic=None`` leaves
records, journal bytes and compiled programs byte-identical (the
disabled-mode parity contract, pinned by the quality gate's ``elastic``
leg).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

#: Resize directions (journal/metric label values).
UP = "up"
DOWN = "down"


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Knobs for the resize decision. Thresholds are *per device*: the
    controller multiplies by the current dp, so the config keeps one
    meaning on any mesh width (the ``--max-batch`` discipline)."""

    #: Grow when outstanding depth stays above ``up_depth · dp`` for
    #: ``up_window_ms`` of virtual time.
    up_depth: int = 8
    up_window_ms: float = 200.0
    #: Shrink when outstanding depth stays below ``down_depth · dp`` for
    #: ``down_window_ms``. The down window is deliberately longer than the
    #: up window (hysteresis): growing is cheap to regret, shrinking under
    #: a lull that was about to end costs a second cutover pause.
    down_depth: int = 2
    down_window_ms: float = 800.0
    #: Minimum virtual-time spacing between committed resizes — the other
    #: half of the anti-flap guarantee.
    cooldown_ms: float = 400.0
    #: dp bounds. ``max_dp=0`` means "what the process has": the engine
    #: resolves it to the largest power of two ≤ local device count.
    min_dp: int = 1
    max_dp: int = 0

    def __post_init__(self):
        if self.min_dp < 1 or self.min_dp & (self.min_dp - 1):
            raise ValueError(
                f"elastic min_dp must be a power of two >= 1, "
                f"got {self.min_dp}")
        if self.max_dp and (self.max_dp < self.min_dp
                            or self.max_dp & (self.max_dp - 1)):
            raise ValueError(
                f"elastic max_dp must be a power of two >= min_dp, "
                f"got {self.max_dp}")
        if self.up_depth <= self.down_depth:
            # The dead band between the two thresholds is the hysteresis;
            # without it a depth sitting on the line grows and shrinks
            # forever.
            raise ValueError(
                f"elastic up_depth ({self.up_depth}) must exceed "
                f"down_depth ({self.down_depth})")


def parse_elastic(spec: str) -> ElasticConfig:
    """Parse the CLI ``--elastic`` value: ``on`` (defaults) or a
    comma-separated ``k=v`` list over the config fields, e.g.
    ``up_depth=8,down_window_ms=800,max_dp=4``."""
    s = spec.strip()
    if s in ("", "on", "default"):
        return ElasticConfig()
    fields = {f.name: f.type for f in dataclasses.fields(ElasticConfig)}
    kw = {}
    for part in s.split(","):
        if "=" not in part:
            raise ValueError(f"--elastic expects 'on' or 'k=v,...', "
                             f"got {spec!r}")
        k, v = part.split("=", 1)
        k = k.strip()
        if k not in fields:
            raise ValueError(f"unknown --elastic field {k!r}; valid: "
                             f"{', '.join(sorted(fields))}")
        kw[k] = (float(v) if "window" in k or "cooldown" in k else int(v))
    return ElasticConfig(**kw)


class ElasticController:
    """The windowed up/down pressure detector plus resize bookkeeping.

    Pure control logic on the engine's virtual clock — no jax, no
    devices, no threads. The engine feeds it the queue depth each loop
    iteration (:meth:`observe`); a non-None return is a *decision* (the
    target dp) which stands until the engine either commits the cutover
    (:meth:`committed`) or the decision becomes stale (depth moved back
    inside the dead band before the cutover ran — :meth:`observe`
    withdraws it)."""

    def __init__(self, config: ElasticConfig, dp: int, ndev: int):
        self.config = config
        self.dp = int(dp)
        max_dp = config.max_dp
        if not max_dp:
            max_dp = 1
            while max_dp * 2 <= ndev:
                max_dp *= 2
        self.max_dp = min(max_dp, pow2_floor(ndev))
        self.min_dp = config.min_dp
        self._pressure_since: Optional[float] = None
        self._calm_since: Optional[float] = None
        self._last_resize: Optional[float] = None
        self.pending_target: Optional[int] = None
        # -- stats the summary/bench sub-record reports -------------------
        self.resizes_up = 0
        self.resizes_down = 0
        self.deferred_slo = 0
        self.prewarm_ms_total = 0.0
        self.pause_ms: List[float] = []
        self.timeline: List[dict] = []

    # -- decision ---------------------------------------------------------
    def observe(self, depth: int, vnow: float,
                premium_waiting: bool = False) -> Optional[int]:
        """Fold one loop iteration's pressure sample. Returns the target
        dp when a resize should run at the next batch boundary, else
        None. ``premium_waiting`` defers *shrink* decisions only."""
        cfg = self.config
        if self._last_resize is not None and \
                vnow - self._last_resize < cfg.cooldown_ms:
            return self.pending_target
        hi = cfg.up_depth * self.dp
        lo = cfg.down_depth * self.dp
        if depth > hi:
            self._calm_since = None
            if self._pressure_since is None:
                self._pressure_since = vnow
            if self.dp < self.max_dp and \
                    vnow - self._pressure_since >= cfg.up_window_ms:
                self.pending_target = self.dp * 2
        elif depth < lo:
            self._pressure_since = None
            if self._calm_since is None:
                self._calm_since = vnow
            if self.dp > self.min_dp and \
                    vnow - self._calm_since >= cfg.down_window_ms:
                if premium_waiting:
                    # Premium traffic never waits on a shrink: hold the
                    # calm timer (the lull is real) but defer the decision
                    # until the premium backlog clears.
                    self.deferred_slo += 1
                    return self.pending_target
                self.pending_target = max(self.min_dp, self.dp // 2)
        else:
            # Inside the dead band: both timers re-arm, and a not-yet-
            # executed decision is withdrawn — the pressure that justified
            # it is gone.
            self._pressure_since = None
            self._calm_since = None
            if self.pending_target is not None:
                self.pending_target = None
        if self.pending_target == self.dp:
            self.pending_target = None
        return self.pending_target

    # -- bookkeeping ------------------------------------------------------
    def committed(self, vnow: float, new_dp: int, *, prewarm_ms: float,
                  pause_ms: float, parked: int, resumed: int) -> dict:
        """The engine finished a cutover: fold the facts, re-arm the
        windows, start the cooldown. Returns the timeline entry."""
        direction = UP if new_dp > self.dp else DOWN
        entry = {"vnow_ms": round(vnow, 3), "old_dp": self.dp,
                 "new_dp": int(new_dp), "direction": direction,
                 "prewarm_ms": round(prewarm_ms, 3),
                 "pause_ms": round(pause_ms, 3),
                 "parked": int(parked), "resumed": int(resumed)}
        self.timeline.append(entry)
        if direction == UP:
            self.resizes_up += 1
        else:
            self.resizes_down += 1
        self.prewarm_ms_total += prewarm_ms
        self.pause_ms.append(pause_ms)
        self.dp = int(new_dp)
        self.pending_target = None
        self._pressure_since = None
        self._calm_since = None
        self._last_resize = vnow
        return entry

    def stats(self) -> dict:
        """The summary's ``elastic`` block."""
        return {"resizes_up": self.resizes_up,
                "resizes_down": self.resizes_down,
                "deferred_slo": self.deferred_slo,
                "prewarm_ms": round(self.prewarm_ms_total, 3),
                "cutover_pause_p95_ms": round(_p95(self.pause_ms), 3),
                "parked": sum(e["parked"] for e in self.timeline),
                "resumed": sum(e["resumed"] for e in self.timeline),
                "timeline": list(self.timeline)}


def pow2_floor(n: int) -> int:
    """Largest power of two ≤ ``n`` (≥ 1) — the widest dp a machine with
    ``n`` devices can host."""
    p = 1
    while p * 2 <= max(1, n):
        p *= 2
    return p


def _p95(xs: List[float]) -> float:
    if not xs:
        return 0.0
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(0.95 * len(ys)))]
