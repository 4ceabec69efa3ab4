"""What the readers of the mesh training cells (driver ``train_mesh``)
share.  ``harness.readers.kind`` knows the one-chip kinds only, so these
readers check the driver and the trace themselves, and return None on
any other cell, on an untraced run, and where the program recorded
nothing for them (no module or op of that name, no counter)."""

from __future__ import annotations

import re

from harness import work
from harness.readers import peaks

DRIVER = "train_mesh"
# "%name = <type> <opcode>(": the result type and the opcode of an HLO op.
_OP = re.compile(r"=\s*(\([^=]*?\)|\S+)\s+([\w.-]+)\(")


def traced(run) -> bool:
    return (run.traffic.get("driver") == DRIVER and run.trace is not None
            and run.trace.devices > 0)


def totals(run) -> dict | None:
    """``repro.obs.totals()`` of a traced mesh run, else None."""
    if not traced(run):
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.totals()


def module_s(run, fragment: str) -> float | None:
    """Device seconds per execution (on one chip) of the modules named
    with ``fragment``."""
    if not traced(run):
        return None
    hit = run.trace.module(fragment)
    if hit is None or hit[1] == 0 or hit[0] <= 0:
        return None
    return hit[0] / hit[1]


def allreduce_s(run, length: int) -> float | None:
    """Device seconds, per chip, of the all-reduce ops whose result is
    a vector of ``length`` (the op's name in the trace is its HLO text:
    ``%all-reduce.3 = f32[8]{0} all-reduce(...)``, or the start and done
    halves of an asynchronous one)."""
    if not traced(run):
        return None
    shape = f"[{int(length)}]"
    total = 0.0
    for name, seconds in run.trace.ops.items():
        m = _OP.search(name)
        if m and "all-reduce" in m.group(2) and shape in m.group(1):
            total += seconds
    return total / run.trace.devices if total > 0 else None


def counter(run, name: str) -> float | None:
    t = totals(run)
    if t is None:
        return None
    return t["counters"].get(name) or None


def share(run, flops: float, nbytes: float, seconds: float) -> float:
    return work.roofline_share(flops, nbytes, seconds, peaks(run))[0]
