"""Share of the margin program's roofline: the required bytes of the
answers scored in the traced part (12 per stored id, 4 per answer), at
peak HBM bandwidth, over the device time of the modules named
``margins_rows``.  Bound by bytes."""

from harness.readers import margin_roofline


def read(run):
    return margin_roofline(run, "bulk")
