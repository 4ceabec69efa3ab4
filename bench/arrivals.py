"""Arrival schedules of open-loop traffic, from a traffic file's numbers.

``rate`` requests per second over the window, ``round(rate * seconds)``
of them: a Poisson process conditioned on its count, so every seed
offers the same number of requests, at its own times.  With ``burst``
(``{"on_s": a, "off_s": b}``) requests arrive only in the on-periods of a
square wave that starts on, at ``rate * (a + b) / a`` while on, so the
mean rate is the same.
"""

from __future__ import annotations

import numpy as np


def schedule(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Sorted offsets in seconds from the window's start."""
    rate = float(traffic["rate"])
    n = int(round(rate * seconds))
    burst = traffic.get("burst")
    if not burst:
        return np.sort(rng.uniform(0.0, seconds, n))
    on, off = float(burst["on_s"]), float(burst["off_s"])
    t_on = np.sort(rng.uniform(0.0, seconds * on / (on + off), n))
    period = np.floor(t_on / on)
    return period * (on + off) + (t_on - period * on)
