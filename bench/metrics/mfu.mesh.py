"""The whole outer iteration's share of one chip's peak: the required
per-chip work (``harness.work_fd.outer_*``: d/q features, nnz/q stored
ids) of the outers completed in the traced part, at peak, over the
traced window.  Bound by bytes."""

from harness import mesh


def read(run):
    if not mesh.traced(run) or not run.counts.get("traced_outers"):
        return None
    k = run.counts["traced_outers"]
    return mesh.share(run, k * run.counts["outer_flops"], k * run.counts["outer_bytes"],
                      run.trace.window_s)
