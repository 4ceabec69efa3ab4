"""Predictions answered per second of the window's whole time."""

from harness.readers import kind


def read(run):
    if kind(run) == "train" or not run.window_s:
        return None
    return run.counts["completed"] / run.window_s
