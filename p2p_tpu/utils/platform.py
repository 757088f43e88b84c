"""Deterministic-CPU platform pinning shared by the analysis drivers.

``tools/jaxcheck.py``, ``tools/quality_gate.py`` and ``p2p-tpu check
--static`` must all see the SAME platform — the deterministic CPU backend
with a virtual multi-device mesh — or their verdicts diverge (a one-device
run degrades the shardcheck dp sweep to dp=1, where every replica group is
degenerate and a real hidden all-gather at dp >= 2 passes unseen). One
helper instead of a copy-pasted env block per driver, so the forcing logic
can only drift in one place.

jax-free by design: this must run before ``import jax`` (JAX reads
``JAX_PLATFORMS`` when it is imported).
"""

from __future__ import annotations

import os

#: The virtual CPU device count every analysis driver (and the test
#: conftest) forces: enough for the dp ∈ {1, 2, 4} shardcheck sweep and
#: the dp=4 mesh-parity drills.
VIRTUAL_DEVICES = 8


def force_cpu_platform(virtual_devices: int = VIRTUAL_DEVICES) -> None:
    """Pin the deterministic CPU backend and (unless the operator already
    pinned a count) the virtual multi-device platform via env vars. An
    operator-set ``xla_force_host_platform_device_count`` in ``XLA_FLAGS``
    is respected verbatim."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count"
            f"={virtual_devices}").strip()
