"""Share of the lanes the full gradient processes that hold no stored
entry: 100 x (1 - `full_grad.stored` / `full_grad.lanes`), the program
counters added at each full-gradient dispatch of the traced part."""

from harness.spans import pad_lane_share


def read(run):
    return pad_lane_share(run, "train")
