"""Model: device time of a call's conditioning, in ms per traced call: what
runs under ``text_encoder/**`` (the towers and the pooling, in the text
programs) and under ``unet/add_embed`` (the embedding added to the time
embedding, ahead of the sampling program's loop). Everything here runs once a
call and not once a step, so it is read outside the loop only. A program with
neither scope (or no scope index) gives nothing to read."""

from benchmarks.lib import scopes

_SCOPES = ("text_encoder", "unet/add_embed")


def read(run):
    scoped = scopes.load(run)
    if (not scoped or not scoped.calls
            or scoped.scoped_pct < scopes.SCOPED_FLOOR_PCT):
        return None
    ns = sum(r.op.dur for r in scoped.rows if not r.op.loop
             and any(r.scope == s or r.scope.startswith(s + "/") for s in _SCOPES))
    return ns / scoped.ndev / scoped.calls / 1e6 if ns else None
