"""Phase hand-off: the unit of transfer between the two program pools.

Phase-disaggregated continuous batching splits a gated request's
trajectory across two separately scheduled pools: a phase-1 program (full
CFG + controller hooks, steps ``[0, gate)``) produces a per-lane
:class:`~p2p_tpu.engine.sampler.PhaseCarry` — ``AttnCache`` + latent + CFG
residual + multistep scheduler state (+ the frozen store), ONE pytree with
a pinned treedef — and a phase-2 program (single-branch U-Net off the
cache) consumes it. This module is everything that crosses the boundary:

- :class:`HandoffEntry` — a queued-and-admitted request whose phase 1 has
  completed, waiting in the phase-2 batcher with its hand-off unit
  (``{"carry": PhaseCarry, "ctx": encoded cond context}`` from the real
  runners — the context rides along so phase 2 never re-runs the text
  encoder). The unit is *opaque* to the engine loop (tests hand fake
  runners fake carries); only the runners and the spill path touch its
  leaves.
- :func:`lane_carries` / :func:`stack_carries` — split a pool program's
  ``(G, ...)``-leading carry into per-lane units and re-pack lanes from
  *different* phase-1 batches into one phase-2 batch (padding replicates
  the last real lane, mirroring the batcher's input-padding contract).
- :func:`spill_carry` / :func:`load_carry` / :func:`carry_template` — the
  journal's crash-replay persistence: a carry round-trips through an
  ``.npz`` next to the WAL, validated leaf-by-leaf against the treedef the
  *request* implies, so a restart resumes the request in phase 2 instead
  of re-running phase 1 — and a corrupt/mismatched spill falls back to
  phase 1 instead of feeding a wrong-shaped carry to a compiled program.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, List, Optional

from .queue import Entry


@dataclasses.dataclass
class HandoffEntry:
    """One request between its phases: the original admission entry plus
    the per-lane carry its phase-1 batch produced. Exposes the same
    surface the batcher/queue code reads off an :class:`Entry`, so the
    phase-2 pool rides the identical machinery (aging, deadlines,
    cancellation, priority ordering)."""

    entry: Entry
    carry: Any                      # per-lane carry (opaque to the engine)
    handoff_ms: float               # virtual time phase 1 completed
    phase1: Optional[dict] = None   # phase-1 latency/batch facts for the record
    resumed: bool = False           # reloaded from a journal spill on replay
    #: A chaos 'nan' fault hit this lane's phase-1 dispatch: validation is
    #: a completion-time verdict, so the injection rides the hand-off and
    #: converts the lane to `invalid_output` at phase 2 — matching the
    #: monolithic engine, where the same injection poisons the one batch.
    nan_injected: bool = False
    #: SLO preemption bookkeeping (serve.scheduling): when this entry was
    #: last parked (None = not currently parked) and the total virtual
    #: time it has spent parked — surfaced in the record's ``phases``
    #: detail and attributed as the flight tracer's ``preempt_wait``
    #: stage. The in-memory carry survives a park (the journal spill is
    #: the *crash* copy, not the working copy), so an in-process resume
    #: is trivially bitwise.
    preempted_ms: Optional[float] = None
    preempt_wait_ms: float = 0.0
    #: ISSUE 13: this entry entered phase 2 off a semantic-cache prefix
    #: hit ("l2") instead of a phase-1 dispatch — a prefix hit IS a
    #: hand-off resume, surfaced as ``phases.phase1.cached`` in the
    #: record rather than ``resumed`` (which names the crash-replay path).
    cache_layer: Optional[str] = None

    @property
    def prepared(self):
        return self.entry.prepared

    @property
    def request(self):
        return self.entry.request

    @property
    def request_id(self) -> str:
        return self.entry.request_id

    @property
    def arrival_ms(self) -> float:
        return self.entry.arrival_ms

    @property
    def seq(self) -> int:
        return self.entry.seq

    @property
    def deadline_at(self) -> Optional[float]:
        return self.entry.deadline_at


def lane_carries(carry: Any, n: int) -> List[Any]:
    """Split a pool program's carry (leaves with a leading G axis) into the
    first ``n`` per-lane carries — the hand-off units. Pure tree indexing:
    works on real :class:`PhaseCarry` pytrees and on whatever fake carry a
    test runner returns, as long as leaves index on axis 0."""
    import jax

    return [jax.tree_util.tree_map(lambda x, i=i: x[i], carry)
            for i in range(n)]


def stack_carries(carries: List[Any], bucket: int, mesh=None) -> Any:
    """Re-pack per-lane carries into a phase-2 batch of ``bucket`` lanes,
    replicating the last real carry into the padding lanes (the same
    padding contract as the input batcher: padded lanes are masked out of
    results by ``lane_select``).

    ``mesh``: on a device mesh the lanes being packed may live on
    *different* shards (they came out of different phase-1 batches, each
    sharded over ``dp``), and ``jnp.stack`` refuses cross-committed
    operands. Each lane is staged straight to its TARGET device
    (explicit device-to-device ``device_put`` — no host round-trip), the
    per-device sub-batches are stacked locally, and the global
    ``P("dp")``-sharded batch is assembled from the shards. No device
    ever holds more than its own ``bucket/dp`` lanes — replicating the
    lanes first would transiently put the whole global batch (carry +
    AttnCache) on every chip, defeating the per-device footprint cap the
    dp-scaled phase-2 width exists to honor."""
    import jax
    import jax.numpy as jnp

    carries = list(carries)
    while len(carries) < bucket:
        carries.append(carries[-1])
    if mesh is None:
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
    from jax.sharding import NamedSharding, PartitionSpec

    devices = list(mesh.devices.flat)
    per_dev = bucket // len(devices)  # whole lanes by bucket construction
    gspec = NamedSharding(mesh, PartitionSpec("dp"))

    def pack(*xs):
        shards = []
        for i, d in enumerate(devices):
            block = [jax.device_put(x, d)
                     for x in xs[i * per_dev:(i + 1) * per_dev]]
            shards.append(jnp.stack(block))  # stays on d: all operands on d
        global_shape = (bucket,) + tuple(xs[0].shape)
        return jax.make_array_from_single_device_arrays(
            global_shape, gspec, shards)

    return jax.tree_util.tree_map(pack, *carries)


# ---------------------------------------------------------------------------
# Journal spill: crash-replay resumes in phase 2
# ---------------------------------------------------------------------------


def carry_template(pipe, prep):
    """The hand-off unit this request's phase-1 runner produces — derived
    from the *request* (shapes only, zero-valued), never from a live carry.
    ``{"carry": PhaseCarry, "ctx": the cond half of the conditioning}``
    ((B, L, D) hidden states, or the preset's ``Conditioning``): the encoded
    conditional half rides the hand-off so phase 2 (and a journal-resumed
    lane) never re-runs the text encoder. This is the pinned-treedef
    source :func:`load_carry` validates a spill against: the spec a spill
    must match is what the phase-2 program was compiled for, which the
    request alone determines."""
    import jax.numpy as jnp

    from ..controllers.base import init_store_state
    from ..engine.sampler import PhaseCarry
    from ..models.conditioning import zeros_for
    from ..models.config import denoiser_layout
    from ..models.unet import init_attn_cache
    from ..ops import schedulers as sched_mod

    b = len(prep.request.prompts)
    cfg = pipe.config
    ctrl = prep.controller
    # the pool programs' layout (``parallel.sweep._phase_args``): the store
    # that crosses the hand-off holds LocalBlend's maps and nothing else
    layout = denoiser_layout(cfg.unet).for_readers(ctrl)
    lat = jnp.zeros((b,) + pipe.latent_shape, jnp.float32)
    state = init_store_state(layout, b)
    sched = getattr(prep, "schedule", None)
    if sched is not None:
        # Per-site reuse schedule (ISSUE 15): the hand-off cache holds one
        # (B, P, C) leaf per EVER-CACHED site of the table (cross or
        # self), not the all-cross AttnCache of the uniform gate — the
        # request's schedule determines the spill spec exactly like it
        # determines the phase programs.
        from ..engine import reuse as reuse_mod

        cache = reuse_mod.init_schedule_cache(layout, sched, b, phase=2,
                                              dtype=lat.dtype)
    else:
        cache = init_attn_cache(layout, b, dtype=lat.dtype)
    carry = PhaseCarry(
        latents=lat,
        resid=jnp.zeros_like(lat),
        cache=cache,
        ms=sched_mod.init_multistep_state(prep.request.scheduler, lat.shape,
                                          lat.dtype),
        state=state)
    return {"carry": carry, "ctx": zeros_for(cfg, b)}


def spill_carry(carry: Any, path: str) -> str:
    """Persist one per-lane carry as an ``.npz`` (leaves in flatten order);
    returns the carry's pinned spec (``engine.sampler.carry_spec``) for the
    journal's ``handoff`` record. Written via a temp file + rename so a
    crash mid-write leaves either the old spill or none — never a torn
    file that parses."""
    import jax
    import numpy as np

    from ..engine.sampler import carry_spec

    leaves = jax.tree_util.tree_flatten(carry)[0]
    host = {f"leaf_{i}": np.asarray(jax.device_get(x))
            for i, x in enumerate(leaves)}
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **host)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return carry_spec(carry)


def load_carry(path: str, template: Any) -> Any:
    """Load a spilled carry, validated leaf-by-leaf (count, shape, dtype)
    against ``template`` (from :func:`carry_template`). Raises
    ``ValueError`` on any mismatch or unreadable file — the caller falls
    back to re-running phase 1 rather than feeding a compiled program a
    carry it was not built for. Leaves are staged back to device
    explicitly (``stage_host``) so a resumed lane dispatches as
    transfer-guard-clean as a fresh one."""
    import jax
    import numpy as np

    from ..engine.sampler import stage_host

    try:
        data = np.load(path)
    except Exception as e:  # noqa: BLE001 — any unreadable spill is a miss
        raise ValueError(f"unreadable carry spill {path!r}: {e}")
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    leaves = []
    for i, tl in enumerate(t_leaves):
        name = f"leaf_{i}"
        if name not in data:
            raise ValueError(f"carry spill {path!r} missing {name} "
                             f"(expected {len(t_leaves)} leaves)")
        arr = data[name]
        if tuple(arr.shape) != tuple(tl.shape) or \
                str(arr.dtype) != str(tl.dtype):
            raise ValueError(
                f"carry spill {path!r} leaf {i}: {arr.shape}/{arr.dtype} "
                f"does not match the request's pinned spec "
                f"{tuple(tl.shape)}/{tl.dtype}")
        leaves.append(stage_host(arr))
    if len(data.files) > len(t_leaves):
        raise ValueError(f"carry spill {path!r} has {len(data.files)} "
                         f"leaves, expected {len(t_leaves)}")
    return jax.tree_util.tree_unflatten(treedef, leaves)
