"""Set-up: seconds inside reads of the persistent cache (``cache_hit`` rows
of the program's compile ledger) but outside the read itself (their
``cache_read`` rows): the cache key, which serializes and hashes the module,
and JAX's bookkeeping around the read.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "cache_key")
