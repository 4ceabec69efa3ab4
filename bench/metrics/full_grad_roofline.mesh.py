"""Share of the mesh full gradient's roofline on one chip: the required
bytes of one chip's full gradient (``harness.work_fd.full_grad_bytes``:
8 per stored id of its block, 8 per block feature, 8 per row) at peak
HBM bandwidth, over the device time per execution of
``jit_mesh_full_grad``.  Bound by bytes."""

from harness import mesh


def read(run):
    s = mesh.module_s(run, "mesh_full_grad")
    nbytes = run.counts.get("full_grad_bytes")
    if s is None or not nbytes:
        return None
    return mesh.share(run, 0.0, nbytes, s)
