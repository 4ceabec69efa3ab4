"""Host us per scored batch inside `serve.engine` but outside its
`serve.h2d`, `serve.dispatch` and `serve.d2h` children: the engine's
own Python glue (the span's self time), over the batches of the traced
part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "bulk", "serve.engine", self_time=True)
    return None if s is None else 1e6 * s
