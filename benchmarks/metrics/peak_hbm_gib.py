"""Peak bytes in use on the fullest device after the window, in GiB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 2 ** 30 if run.on_chip and peak else None
