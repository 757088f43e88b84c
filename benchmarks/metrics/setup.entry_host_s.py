"""Set-up: the entry points' own host work, seconds inside the program's
``entry.*`` and ``sampler.*`` spans (its span ring) and outside tracing,
lowering, the cache, compiles and collections.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "entry_host")
