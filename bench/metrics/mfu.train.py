"""The whole outer iteration's share of the chip's peak: the required
work (harness.work.svrg_outer_*) of the outers completed in the traced
part, at peak, over the traced window.  Bound by bytes."""

from harness import work
from harness.readers import peaks, traced


def read(run):
    if not traced(run, "train") or not run.counts.get("traced_outers"):
        return None
    k = run.counts["traced_outers"]
    return work.roofline_share(k * run.counts["outer_flops"], k * run.counts["outer_bytes"],
                               run.trace.window_s, peaks(run))[0]
