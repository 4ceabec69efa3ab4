"""Host us per scored batch uploading it (`jnp.asarray` of indices and
values): the program span `serve.h2d` inside `serve.engine`, over the
batches of the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "bulk", "serve.h2d", per="serve.engine")
    return None if s is None else 1e6 * s
