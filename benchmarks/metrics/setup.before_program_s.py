"""Set-up: seconds from process start to ``CompileLedger.started_at``, the
first instant the program is in charge: imports and the backend's start.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "before_program")
