"""Device us per inner step on one chip: the mesh inner-epoch program's
(XLA module ``jit_mesh_inner_epoch``) device time per execution over its
M steps."""

from harness import mesh


def read(run):
    s = mesh.module_s(run, "mesh_inner_epoch")
    return None if s is None else 1e6 * s / run.counts["inner_steps"]
