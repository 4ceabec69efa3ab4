"""Device us per inner step: the inner-epoch program's (XLA module
``_inner_epoch``) device time per execution over its M steps."""

from harness.readers import module_ms


def read(run):
    ms = module_ms(run, "train", "_inner_epoch")
    return None if ms is None else 1e3 * ms / run.counts["inner_steps"]
