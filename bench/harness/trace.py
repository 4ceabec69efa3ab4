"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

The traced window is the host span named :data:`WINDOW` that the drivers
put around the part of the run they trace.  Inside it:

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged over
  the devices that ran anything;
* device time and executions per XLA module (the ``XLA Modules`` line);
* device time per op name, for the breakdown;
* idle time split by what the host was doing: each stretch of a gap
  goes to the innermost host span open on the driver's thread then, a
  span of the benchmark's own (``solve``, ``submit``, ``flush``,
  ``engine.margins``) or one JAX records itself (``PjitFunction(...)``,
  ``np.asarray(jax.Array)``), or to "none" where no span was open.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    modules: dict[str, list[float]]  # name -> [seconds, executions]
    ops: dict[str, float]  # name -> seconds
    idle: dict[str, float]  # host span name -> idle seconds
    devices: int

    def module(self, *fragments: str) -> tuple[float, int] | None:
        """Seconds and executions of the modules whose name holds every
        fragment; None when no module matches."""
        hit = [v for k, v in self.modules.items()
               if all(f in k for f in fragments)]
        if not hit:
            return None
        return sum(v[0] for v in hit), int(sum(v[1] for v in hit))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(directory: str | os.PathLike) -> Path:
    hits = sorted(glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(hits[-1])


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _innermost(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into segments, each named by the innermost of the
    (nested, start-sorted) host spans open in it, or "none"."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    cur = lo

    def emit(end: float, name: str) -> None:
        nonlocal cur
        a, b = max(cur, lo), min(end, hi)
        if b > a:
            out.append((a, b, name))
        cur = max(cur, end)

    for span in spans:
        while stack and stack[-1][1] <= span[0]:
            top = stack.pop()
            emit(top[1], top[2])
        emit(span[0], stack[-1][2] if stack else "none")
        stack.append(span)
    while stack:
        top = stack.pop()
        emit(top[1], top[2])
    emit(hi, "none")
    return out


def _host_window(planes) -> tuple[float, float, list]:
    """The WINDOW span and the host line (thread) it was recorded on."""
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return ev.start_ns, ev.end_ns, line
    raise ValueError(f"no host span named {WINDOW!r} in the trace")


def reduce_planes(planes) -> Reduced:
    planes = list(planes)
    lo, hi, host_line = _host_window(planes)
    host = sorted(
        ((ev.start_ns, ev.end_ns, ev.name) for ev in host_line.events
         if ev.name != WINDOW and _clip(ev.start_ns, ev.end_ns, lo, hi)),
    )
    modules: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    busy_total, devices, gaps_per_device = 0.0, 0, []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        intervals = []
        for ev in lines[OPS_LINE].events if OPS_LINE in lines else ():
            iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if iv:
                intervals.append(iv)
                ops[ev.name] = ops.get(ev.name, 0.0) + (iv[1] - iv[0]) * 1e-9
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if iv:
                m = modules.setdefault(ev.name, [0.0, 0])
                m[0] += (iv[1] - iv[0]) * 1e-9
                m[1] += 1
        if not intervals:
            continue
        devices += 1
        merged = _union(intervals)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps_per_device.append([(edges[i], edges[i + 1])
                                for i in range(0, len(edges), 2)
                                if edges[i + 1] > edges[i]])
    idle: dict[str, float] = {}
    segments = _innermost(host, lo, hi)
    for gaps in gaps_per_device:
        k = 0
        for a, b in gaps:
            while k < len(segments) and segments[k][1] <= a:
                k += 1
            i = k
            while i < len(segments) and segments[i][0] < b:
                s0, s1, name = segments[i]
                part = min(b, s1) - max(a, s0)
                if part > 0:
                    idle[name] = idle.get(name, 0.0) + part * 1e-9 / devices
                i += 1
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / devices if devices else 0.0,
        modules=modules, ops=ops, idle=idle, devices=devices,
    )


def reduce_file(path: str | os.PathLike) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)
