"""The blocked float64 reference agrees with the plain one on the same
rows: margins, and SVRG's objectives, gradient norms and iterate."""

import numpy as np
import pytest

from harness import core

REFS = core.BENCH / "refs"


def _rows(n=60, dim=1001, seed=0):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, 40, size=n)
    ids = np.zeros((n, 40), np.int32)
    vals = np.zeros((n, 40), np.float32)
    for i, k in enumerate(lengths):
        ids[i, :k] = np.sort(rng.choice(dim, size=k, replace=False))
        v = rng.gamma(2.0, 1.0, size=k)
        vals[i, :k] = v / np.linalg.norm(v)
    labels = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    return ids, vals, labels, lengths


def _blocks(ids, vals, lengths, bounds):
    """Per block (indptr, local ids, values, lo, hi) of the padded rows."""
    out = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        keep = (ids >= lo) & (ids < hi) & (np.arange(ids.shape[1]) < lengths[:, None])
        counts = keep.sum(axis=1)
        out.append((np.concatenate([[0], np.cumsum(counts)]), ids[keep] - lo,
                    vals[keep], lo, hi))
    return out


@pytest.mark.parametrize("q", [1, 3, 4])
def test_blocked_reference_matches_plain(q):
    linear = core.load_module(REFS / "linear.py")
    blocked = core.load_module(REFS / "linear_fd.py")
    ids, vals, labels, lengths = _rows()
    dim = 1001
    size = -(-dim // q)
    blocks = _blocks(ids, vals, lengths, [l * size for l in range(q + 1)])
    w = np.random.default_rng(1).normal(size=dim)
    np.testing.assert_allclose(blocked.margins(blocks, np.pad(w, (0, q * size - dim))),
                               linear.margins(ids, vals, w), rtol=1e-12, atol=1e-15)
    kw = dict(dim=dim, lam=1e-3, eta=0.5, u=4, m=15, calls=[(3, 2), (4, 1)])
    got = blocked.svrg(blocks, labels, **kw)
    want = linear.svrg(ids, vals, labels, **kw)
    np.testing.assert_allclose(got["objectives"], want["objectives"], rtol=1e-12)
    np.testing.assert_allclose(got["grad_norms"], want["grad_norms"], rtol=1e-12)
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-12,
                               atol=1e-12 * np.abs(want["w"]).max())
