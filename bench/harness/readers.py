"""What the metric readers under ``bench/metrics`` share.

A reader returns None where its run has nothing for it to read (another
kind of cell, no trace, no module of that name in the trace), never 0;
a metric that the cell lists and that reads None fails the run
(``core.Run.result``).

Cell kinds: ``train`` (driver ``train``), ``steady`` (open-loop
serving) and ``bulk`` (closed-loop serving).
"""

from __future__ import annotations

import numpy as np

from harness import work


def kind(run) -> str:
    if run.traffic["driver"] == "train":
        return "train"
    return "steady" if run.traffic["loop"] == "open" else "bulk"


def peaks(run) -> dict:
    return work.load_peaks(run.devices[0].device_kind)


def traced(run, of: str) -> bool:
    """Whether the run is of kind ``of`` and its trace saw a device."""
    return kind(run) == of and run.trace is not None and run.trace.devices > 0


def idle_share(run, of: str):
    if not traced(run, of) or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def module_ms(run, of: str, fragment: str):
    """Device ms per execution of the modules named with ``fragment``."""
    if not traced(run, of):
        return None
    hit = run.trace.module(fragment)
    if hit is None or hit[1] == 0:
        return None
    return 1e3 * hit[0] / hit[1]


def margin_roofline(run, of: str):
    """Required bytes of the answers scored in the traced part over the
    device time of the margin programs there, at peak bandwidth."""
    if not traced(run, of):
        return None
    hit = run.trace.module("margins_rows")
    if hit is None or hit[0] <= 0 or not run.counts.get("traced_requests"):
        return None
    ids, reqs = run.counts["traced_ids"], run.counts["traced_requests"]
    return work.roofline_share(work.margin_flops(ids), work.margin_bytes(ids, reqs),
                               hit[0], peaks(run))[0]


def serve_mfu(run, of: str):
    """Required bytes of every answer in the traced part over the traced
    window at peak bandwidth: the whole serving path's share."""
    if not traced(run, of) or not run.counts.get("traced_requests"):
        return None
    ids, reqs = run.counts["traced_ids"], run.counts["traced_requests"]
    return work.roofline_share(work.margin_flops(ids), work.margin_bytes(ids, reqs),
                               run.trace.window_s, peaks(run))[0]


def engine_ms(run, of: str):
    e = run.samples.get("engine_s")
    if kind(run) != of or e is None or not len(e):
        return None
    return 1e3 * float(np.mean(e))
