"""Host us per flushed batch copying its requests into the padded
`[rows, width]` arrays (the program span `serve.pack` in
`MicroBatcher._flush`), over the batches of the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "bulk", "serve.pack")
    return None if s is None else 1e6 * s
