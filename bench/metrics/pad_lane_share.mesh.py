"""Share of the lanes the mesh full gradient processes, over all chips,
that hold no stored entry: 100 x (1 - `full_grad.stored` /
`full_grad.lanes`), program counters added at each full-gradient
dispatch of the traced part."""

from harness import mesh


def read(run):
    lanes = mesh.counter(run, "full_grad.lanes")
    stored = mesh.counter(run, "full_grad.stored")
    if lanes is None or stored is None:
        return None
    return 100.0 * (1.0 - stored / lanes)
