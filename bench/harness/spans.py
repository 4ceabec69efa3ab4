"""What the readers of the program's own spans and counters share.

The program records them in ``repro.obs`` while the profiler collects,
which in a run is from ``Run.window_opens`` to ``Run.trace_stops``: the
totals cover exactly the traced part of the window.  A reader returns
None on an untraced run, on a cell of another kind, on a program that
has no ``repro.obs``, and where its span or counter recorded nothing.
"""

from __future__ import annotations

from harness.readers import traced


def totals(run, of: str) -> dict | None:
    """``repro.obs.totals()`` of a traced run of kind ``of``, else None."""
    if not traced(run, of):
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.totals()


def mean_s(run, of: str, name: str, *, per: str | None = None,
           self_time: bool = False) -> float | None:
    """Seconds of the span ``name`` (its self time with ``self_time``)
    per execution of the span ``per`` (default: ``name`` itself)."""
    t = totals(run, of)
    if t is None:
        return None
    span, unit = t["spans"].get(name), t["spans"].get(per or name)
    if not span or not unit or not unit["count"]:
        return None
    return span["self_seconds" if self_time else "seconds"] / unit["count"]


def pad_lane_share(run, of: str) -> float | None:
    """Percent of the full gradient's lanes that hold no stored entry."""
    t = totals(run, of)
    if t is None:
        return None
    counters = t["counters"]
    lanes = counters.get("full_grad.lanes")
    if not lanes or "full_grad.stored" not in counters:
        return None
    return 100.0 * (1.0 - counters["full_grad.stored"] / lanes)
