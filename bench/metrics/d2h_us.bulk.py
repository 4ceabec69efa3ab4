"""Host us per scored batch bringing the margins back (`np.asarray`):
the program span `serve.d2h` inside `serve.engine`, over the batches of
the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "bulk", "serve.d2h", per="serve.engine")
    return None if s is None else 1e6 * s
