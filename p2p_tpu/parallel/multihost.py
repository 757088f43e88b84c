"""Multi-host launch helpers: the DCN-facing half of the distributed backend.

The reference has no distributed code at all (SURVEY §2: single process,
single device); this module supplies the TPU-native equivalent of a
NCCL/MPI-style launcher for pod slices and multi-host CPU/GPU clusters:

- one JAX process per host, connected through :func:`initialize` (a thin,
  env-driven wrapper over ``jax.distributed.initialize`` — the JAX runtime
  then exchanges device topology over DCN);
- a :func:`global_mesh` whose axes are laid out so that *model* axes (tp, sp)
  stay within a host's ICI domain and only the embarrassingly-parallel ``dp``
  axis crosses hosts — edit groups are self-contained (the P2P base/edit
  co-location constraint, `parallel/mesh.py`), so the sampling loop still
  runs with zero cross-host collectives; gathers ride DCN once at the end.

On a single host this degrades to the local mesh (initialize() is a no-op
without coordinator env vars), so the same driver script runs anywhere.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the multi-process JAX runtime; returns True if distributed mode
    is active.

    Arguments default from the conventional env vars
    (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``;
    on Cloud TPU pods ``jax.distributed.initialize()`` auto-discovers all
    three). With no coordinator configured this is a no-op single-process
    setup — scripts stay launcher-agnostic."""
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    # Keep None when unset: jax.distributed.initialize auto-detects
    # num_processes/process_id from cluster envs (SLURM, OpenMPI, TPU
    # metadata, ...) only when they arrive as None.
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    def _int_env(name):
        try:
            return int(os.environ.get(name, "1") or "1")
        except ValueError:
            return 1

    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    if num_processes == 1:
        return False  # explicitly single-process: nothing to join
    # A coordinator address (or >1 processes) is an explicit multi-process
    # signal; bare process_id/num_processes without one cannot reach jax's
    # initialize (it requires a coordinator), so they don't count alone.
    explicit = coordinator_address is not None or (num_processes or 0) > 1
    cluster = (len(hosts) > 1
               or _int_env("SLURM_JOB_NUM_NODES") > 1
               or _int_env("OMPI_COMM_WORLD_SIZE") > 1)
    if not explicit and not cluster:
        return False  # nothing indicates a multi-process launch

    # initialize() must precede first backend use; jax raises a clear
    # RuntimeError itself when it does not.
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_count() > 1


def global_mesh(tp: int = 1, axis_names: Tuple[str, str] = ("dp", "tp")) -> Mesh:
    """A (dp, tp) mesh over *all* processes' devices, tp innermost.

    ``jax.devices()`` after :func:`initialize` returns the global device list
    ordered process-major, so reshaping to (-1, tp) keeps each tp group on
    one host's ICI domain as long as ``tp`` divides the per-host device
    count — asserted here, because a tp group spanning DCN would turn every
    attention/FF psum into a cross-host collective."""
    per_host = jax.local_device_count()
    if tp > 1 and per_host % tp != 0:
        raise ValueError(
            f"tp={tp} does not divide the per-host device count {per_host}; "
            "a tp group would span DCN")
    from .mesh import make_mesh

    return make_mesh(tp=tp, axis_names=axis_names)


def process_groups(n_groups: int) -> range:
    """The slice of ``range(n_groups)`` this process owns under a dp layout —
    for host-side work (file IO, seeding) that must partition like the mesh."""
    pid, pcount = jax.process_index(), jax.process_count()
    per = (n_groups + pcount - 1) // pcount
    return range(pid * per, min((pid + 1) * per, n_groups))
