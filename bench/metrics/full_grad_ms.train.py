"""Device ms per full-gradient program (XLA module ``_full_grad_blocks``)."""

from harness.readers import module_ms


def read(run):
    return module_ms(run, "train", "_full_grad_blocks")
