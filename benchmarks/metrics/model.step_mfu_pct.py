"""Model: operations the configuration's mathematics needs for all work
completed in the traced window (U-Net forwards at the batch each step really
has, text encodes, decodes; ``lib/flops.py``), over the window's wall seconds
x chips x the chip's bf16 peak. The whole step's share of the peak, which
bounds what any one kernel's roofline can claim."""

from benchmarks.lib import flops
from benchmarks.lib.peaks import peaks_for


def read(run):
    recs = run.traced_records()
    if not run.on_chip or run.trace_data is None or not recs:
        return None
    w = run.work_of(recs)
    total = flops.work_flops(run.config, w["unet_rows_full"], w["unet_rows_cached"],
                             w["prompts"], w["images"])
    seconds = max(r["t_end"] for r in recs) - min(r["t_start"] for r in recs)
    peak = peaks_for(run.device["kind"])["flops_per_s"] * run.cell["chips"]
    return 100.0 * total / (seconds * peak)
