"""The whole serving path's share of the chip's peak: required bytes of
every answer in the traced part, at peak HBM bandwidth, over the traced
window.  Bound by bytes."""

from harness.readers import serve_mfu


def read(run):
    return serve_mfu(run, "bulk")
