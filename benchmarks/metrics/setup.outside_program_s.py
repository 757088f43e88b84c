"""Set-up: seconds no part of the program covers: the harness, the weight
fill's execution, the warm-up calls' device time and their landing.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "outside_program")
