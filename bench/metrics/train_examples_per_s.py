"""Rows visited per second of the window: N per full-gradient pass and u
per inner step, over every outer iteration completed in the window,
divided by the window's whole time."""


def read(run):
    if "rows_visited" not in run.counts or not run.window_s:
        return None
    return run.counts["rows_visited"] / run.window_s
