"""Compile / cache: programs that set-up compiled on the backend although the
persistent cache is on: compiles that followed a cache miss or were never
offered to the cache (``backend`` rows of the program's compile ledger; a
read of the cache is a ``cache_hit`` row). On a warm run these are the
programs JAX never caches."""

from benchmarks.lib import scopes


def read(run):
    rows = scopes.ledger_rows(run, "backend")
    return None if rows is None else len(rows)
