"""Row lengths (stored ids per row), drawn so that every seed gets the
same set of lengths, in its own order: the work of a run does not depend
on its seed.

A spec is ``{"dist": "fixed"}`` (every row holds ``fixed`` ids) or
``{"dist": "lognormal", "median", "sigma", "min", "max"}``: the
quantiles at (i + 1/2) / count of a lognormal of that median and sigma,
rounded and clipped to [min, max].
"""

from __future__ import annotations

import numpy as np

from harness import seeds


def draw(spec: dict | None, count: int, seed: int, *, fixed: int,
         total: int | None = None) -> np.ndarray:
    """int32[count] row lengths.  ``total``: the lengths are moved by one
    id each, over the rows nearest the median, until they sum to it, so
    that a data set keeps its published count of stored entries."""
    spec = spec or {"dist": "fixed"}
    if spec["dist"] == "fixed":
        out = np.full(count, int(fixed), np.int32)
    elif spec["dist"] == "lognormal":
        from statistics import NormalDist

        q = (np.arange(count) + 0.5) / count
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        out = np.clip(np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z)),
                      int(spec["min"]), int(spec["max"])).astype(np.int32)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    if total is not None:
        diff = int(total) - int(out.sum())
        k = abs(diff)
        if k > count:
            raise ValueError(f"lengths sum to {out.sum()}, {k} from {total}: "
                             "more than one id a row")
        lo = count // 2 - k // 2
        out[lo:lo + k] += np.int32(np.sign(diff))
    return seeds.rng(seed, seeds.LENGTHS).permutation(out)
