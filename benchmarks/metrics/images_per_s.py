"""Images of all requests completed in the window, over the wall seconds from
the first request issued to the last completion (the drain is inside)."""


def read(run):
    t0, t1 = run.window
    images = sum(r["images"] for r in run.done)
    return images / (t1 - t0) if images and t1 > t0 else None
