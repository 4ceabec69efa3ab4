"""Device us per inner step on one chip in the per-step all-reduce of the
u partial margins: the all-reduce ops whose result is f32[u] (the full
gradient's is an N-vector), over the steps of the traced epochs (the
program counter ``mesh.allreduce_steps``)."""

from harness import mesh


def read(run):
    steps = mesh.counter(run, "mesh.allreduce_steps")
    s = mesh.allreduce_s(run, int(run.traffic["batch_size"]))
    if steps is None or s is None:
        return None
    return 1e6 * s / steps
