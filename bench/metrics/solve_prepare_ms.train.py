"""Host ms per `solve()` call before its outer loop starts (the program
span `solve.prepare`: spec resolution, partition, BlockCSR cache, rule
and context, `init_w`), over the calls of the traced part."""

from harness.spans import mean_s


def read(run):
    s = mean_s(run, "train", "solve.prepare")
    return None if s is None else 1e3 * s
