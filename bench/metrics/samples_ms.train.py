"""Host ms per outer iteration drawing the M x u sample ids and step mask
and putting them on the device (the program span `outer.samples`), over
the outers of the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "train", "outer.samples", per="outer")
    return None if s is None else 1e3 * s
