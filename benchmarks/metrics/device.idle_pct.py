"""Device: share of the traced window in which no operation ran on the
device, mean over the cell's devices."""


def read(run):
    busy, window = run.device.get("busy_s"), run.device.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)
