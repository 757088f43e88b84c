"""Set-up: seconds of programs compiled on the backend although the cache is
on (``backend`` and ``cache_miss`` rows of the program's compile ledger; a
traced run names them on stderr, with their seconds).
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "uncached_compile")
