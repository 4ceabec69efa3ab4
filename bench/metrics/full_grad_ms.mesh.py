"""Device ms per mesh full-gradient program on one chip (XLA module
``jit_mesh_full_grad``: the row groups' gathers and scatters and the
all-reduce of the N margins)."""

from harness import mesh


def read(run):
    s = mesh.module_s(run, "mesh_full_grad")
    return None if s is None else 1e3 * s
