"""Entry: the slowest call of the window in wall seconds. Stands beside
``images_per_s`` where a window holds too few calls for a 95th percentile to
carry a bound."""


def read(run):
    times = [r["t_end"] - r["t_start"] for r in run.done]
    return max(times) if times else None
