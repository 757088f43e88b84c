"""Model: device time under ``vae.decode/**`` per image decoded in the traced
window, in ms. ``model.decode_ms_per_image`` times the same layer from outside
(everything of the sampling program outside its loop, staging included)."""

from benchmarks.lib import scopes


def read(run):
    scoped = scopes.load(run)
    return scoped.ms_per_image("vae.decode") if scoped else None
