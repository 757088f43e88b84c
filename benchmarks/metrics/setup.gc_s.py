"""Set-up: seconds of collections of Python's cyclic garbage collector (the
rows of the program's ``CollectorWatch``), the harness's ``gc.collect()``
before ``gc.freeze()`` among them.
One of the eight classes of ``lib/setup_parts.py``, which sum to
``setup_s``."""

from benchmarks.lib import setup_parts


def read(run):
    return setup_parts.part(run, "gc")
