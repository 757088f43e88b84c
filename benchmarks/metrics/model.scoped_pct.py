"""Model: share of the traced window's device time whose instruction the
program's scope index places in a named scope, in %. Under 90 the index is
not of the programs that ran (a stale cache entry compiled before the scopes
were named, PERF.md) and the scope metrics read nothing."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.scoped_pct if scoped else None
