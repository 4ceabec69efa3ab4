"""Share of the traced window in which no operation ran on a chip
(averaged over the chips that ran anything)."""

from harness import mesh


def read(run):
    if not mesh.traced(run) or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
