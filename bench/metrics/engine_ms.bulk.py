"""Host ms inside PredictionEngine.margins per batch (H2D, dispatch,
device, D2H), over the batches of the traced part of the window."""

from harness.readers import engine_ms


def read(run):
    return engine_ms(run, "bulk")
