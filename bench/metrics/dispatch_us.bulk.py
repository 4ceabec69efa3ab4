"""Host us per scored batch dispatching the jitted margin call: the
program span `serve.dispatch` inside `serve.engine`, over the batches of
the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "bulk", "serve.dispatch", per="serve.engine")
    return None if s is None else 1e6 * s
