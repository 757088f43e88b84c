"""What the serve engine's records say about its dispatches."""

from __future__ import annotations


def dispatches(records) -> dict:
    """``{batch id: (kind, lanes, occupancy, run_ms, gate_step)}`` of the
    dispatches that served ``records`` (the harness's rows, each with the
    engine's own record under ``engine``); kind is ``mono``, ``phase1`` or
    ``phase2``. A request that went through the two pools names both of its
    batches."""
    out = {}
    for r in records:
        e = r.get("engine", {})
        if "phases" in e:
            for kind in ("phase1", "phase2"):
                p = e["phases"][kind]
                out[p["batch_id"]] = (kind, p["lanes"], p["occupancy"], p["run_ms"],
                                      e["gate_step"])
        elif "batch_id" in e:
            out[e["batch_id"]] = ("mono", e["batch_lanes"], e["batch_occupancy"],
                                  e["run_ms"], e.get("gate_step"))
    return out
