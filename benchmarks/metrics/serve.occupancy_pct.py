"""Serve engine: lanes that carried a request over lanes dispatched, summed
over the dispatches of the traced window (a padded lane is computed and
thrown away)."""

from benchmarks.lib.records import dispatches


def read(run):
    batches = dispatches(run.traced_records())
    lanes = sum(b[1] for b in batches.values())
    return 100.0 * sum(b[2] for b in batches.values()) / lanes if lanes else None
