"""Set-up: seconds of reads of the persistent cache as JAX times them
(``cache_read`` rows of the program's compile ledger, from
``/jax/compilation_cache/cache_retrieval_time_sec``): the entry read,
decompressed and loaded as an executable.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "cache_read")
