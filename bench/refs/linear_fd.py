"""Plain reference of a linear classifier whose features come in blocks:
margins, and SVRG with the logistic loss and an L2 penalty (the paper's
Algorithm 1 = Algorithm 2, Option I), over each block's rows.

It imports nothing of the program.  A block is ``(indptr, ids, vals,
lo, hi)``: the block's stored entries of every row in CSR form (row i's
at ``indptr[i]:indptr[i+1]``), ids local to the block, which covers
global ids ``[lo, hi)``.  A margin is the sum of the blocks' partial margins; a
full gradient is one block-local product per block, over stored entries
only, in float64 (``scipy.sparse``, one thread a block; the inner
steps call its row-product routines directly).

``svrg`` follows ``linear.py``: the sample stream is one
``numpy.random.default_rng(seed)`` per call of the solver, and per outer
``integers(0, n, size=(m, u), dtype=int64)``; the dense per-step term
``z + lam * w`` is carried in closed form, so a step costs the u sampled
rows only.  ``svrg_in`` is the same algorithm written step by step in
``jax.numpy`` over blocks that sit one per device of a mesh, in a dtype
of the caller's choosing, with the faults a broken program could have:
in bfloat16 it is the control a float32 program must be told apart
from.
"""

from __future__ import annotations

import concurrent.futures
import functools
import sys
from pathlib import Path

import numpy as np
from scipy.sparse import _sparsetools as _spt

sys.path.insert(0, str(Path(__file__).resolve().parent))
from linear import _dlogistic, _objective, _outer_samples  # noqa: E402


def _csr(block):
    import scipy.sparse as sp

    indptr, ids, vals, lo, hi = block
    return sp.csr_matrix((np.asarray(vals, np.float64), ids, indptr),
                         shape=(indptr.size - 1, hi - lo))


def _pool(fn, items):
    with concurrent.futures.ThreadPoolExecutor(max_workers=len(items)) as ex:
        return list(ex.map(fn, items))


def margins(blocks, w) -> np.ndarray:
    """float64 margins: per block the product of its rows with its slice
    of the global ``w``, summed over blocks."""
    w = np.asarray(w, np.float64)

    def part(block):
        lo, hi = block[3], block[4]
        return _csr(block) @ w[lo:hi]

    return np.sum(_pool(part, list(blocks)), axis=0)


class _Blocks:
    """The blocks as float64 CSR matrices, each with its slice of w."""

    def __init__(self, blocks, dim):
        self.raw = list(blocks)
        self.x = _pool(_csr, self.raw)
        self.span = [(b[3], b[4]) for b in self.raw]
        self.dim = dim

    def margins(self, w):
        if not w.any():
            return np.zeros(self.x[0].shape[0])
        return np.sum(_pool(lambda k: self.x[k] @ w[slice(*self.span[k])],
                            range(len(self.x))), axis=0)

    def full_grad(self, w, y):
        s = self.margins(w)
        coef = _dlogistic(s, y) / y.shape[0]
        z = np.zeros(self.dim)
        for k, part in enumerate(_pool(lambda k: self.x[k].T @ coef, range(len(self.x)))):
            z[slice(*self.span[k])] = part
        return z, s


def _epoch(data, v, samples, *, xz, s0, y, c, eta):
    """One inner epoch in closed form (``svrg``'s): returns ``a`` and
    updates ``v`` in place.  A step's sampled rows are gathered per
    block and go through scipy's CSR routines directly: the margins as
    a row product, the update as the transpose product (``v[ids] +=
    coef_row * val`` row by row)."""
    m, u = samples.shape
    per_block = [(v[lo:hi], x.indptr, x.indices, x.data, hi - lo)
                 for x, (lo, hi) in zip(data.x, data.span)]
    margins = np.zeros(u)
    a = 0.0
    for k, rows in enumerate(samples):
        margins[:] = 0.0
        steps = []
        for vb, p, ids, vals, size in per_block:
            starts, ends = p[rows], p[rows + 1]
            rp = np.zeros(u + 1, ids.dtype)
            np.cumsum(ends - starts, out=rp[1:])
            idx = np.concatenate([ids[lo:hi] for lo, hi in zip(starts, ends)])
            val = np.concatenate([vals[lo:hi] for lo, hi in zip(starts, ends)])
            _spt.csr_matvec(u, size, rp, idx, val, vb, margins)
            steps.append((vb, rp, idx, val, size))
        s = c**k * margins + a * xz[rows]
        coef = (-eta / c ** (k + 1) / u) * (
            _dlogistic(s, y[rows]) - _dlogistic(s0[rows], y[rows]))
        for vb, rp, idx, val, size in steps:
            _spt.csc_matvec(size, u, rp, idx, val, coef, vb)
        a = c * a - eta
    return a


def svrg(blocks, labels, *, dim, lam, eta, u, m, calls) -> dict:
    """float64 SVRG from w = 0 through ``calls``, a list of ``(seed,
    outers)``, each call warm-started from the last one's iterate: the
    objective after each outer, the gradient norm after the first, and
    the final iterate (global, ``dim`` long; the blocks may cover a few
    columns more, which no row stores)."""
    blocks = list(blocks)
    data = _Blocks(blocks, max(dim, blocks[-1][4]))
    y = np.asarray(labels, np.float64)
    n = y.shape[0]
    c = 1.0 - eta * lam
    w = np.zeros(data.dim)
    z, s0 = data.full_grad(w, y)
    objectives, grad_norms = [], []
    for samples in _outer_samples(calls, n, m, u):
        # w_k = c**k * v + a * z, with v and a updated per step: the
        # dense part of each step is the two scalars, and a row's share
        # of it, a * (x_i . z), is read from x z.
        v = w.copy()
        a = _epoch(data, v, samples, xz=data.margins(z), s0=s0, y=y, c=c, eta=eta)
        w = c**m * v + a * z
        z, s0 = data.full_grad(w, y)
        objectives.append(_objective(s0, y, w, lam))
        grad_norms.append(float(np.linalg.norm(z + lam * w)))
    return {"objectives": objectives, "grad_norms": grad_norms, "w": w[:dim]}


@functools.lru_cache(maxsize=None)
def _epoch_fn(mesh, axes, dtype_name: str, block: int, u: int, fault: str | None,
              chunk: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    dt = jnp.dtype(dtype_name)
    used = u // 2 if fault == "half_batch" else u
    rows_spec = P(axes, None)

    def dphi(s, y):
        return -y * jax.nn.sigmoid(-y * s)

    def full_grad(ids, vals, y, w):
        # ``chunk`` rows at a time, so that no [N, B] product is held.
        n = ids.shape[0]

        def rows(c, a):
            return jax.lax.dynamic_slice_in_dim(a, c * chunk, chunk)

        def margins(c, s):
            part = jnp.sum(w[rows(c, ids)] * rows(c, vals), axis=1, dtype=dt)
            return jax.lax.dynamic_update_slice_in_dim(s, part, c * chunk, 0)

        s = jax.lax.fori_loop(0, n // chunk, margins, jnp.zeros((n,), dt))
        s = jax.lax.psum(s, axes)
        coef = (dphi(s, y) / y.shape[0]).astype(dt)

        def scatter(c, z):
            part = rows(c, vals) * rows(c, coef)[:, None]
            return z.at[rows(c, ids).ravel()].add(part.ravel())

        z = jax.lax.fori_loop(0, n // chunk, scatter, jnp.zeros((block,), dt))
        return z, s

    def epoch(ids, vals, y, w, z, s0, samples, eta, lam):
        def step(w, rows):
            rows = rows[:used]
            idx, val, yk = ids[rows], vals[rows], y[rows]
            s = jax.lax.psum(jnp.sum(w[idx] * val, axis=1, dtype=dt), axes)
            coef = ((dphi(s, yk) - dphi(s0[rows], yk)) / used).astype(dt)
            g = jnp.zeros((block,), dt).at[idx.ravel()].add((val * coef[:, None]).ravel())
            return (w - eta * (g + z + lam * w)).astype(dt), None

        w, _ = jax.lax.scan(step, w, samples)
        return w

    def report(s, y, w, z, lam):
        obj = jnp.mean(jnp.logaddexp(0.0, -y * s)) + 0.5 * lam * jax.lax.psum(
            jnp.sum(w * w), axes)
        return obj, jnp.sqrt(jax.lax.psum(jnp.sum((z + lam * w) ** 2), axes))

    def smap(f, ins, outs):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=ins, out_specs=outs,
                                     check_vma=False))

    return (
        smap(full_grad, (rows_spec, rows_spec, P(), P(axes)), (P(axes), P())),
        smap(epoch, (rows_spec, rows_spec, P(), P(axes), P(axes), P(), P(), P(), P()),
             P(axes)),
        smap(report, (P(), P(), P(axes), P(axes), P()), (P(), P())),
    )


def svrg_in(dtype_name, ids, vals, labels, *, mesh, axes, dim, lam, eta, u, m,
            calls, fault: str | None = None) -> dict:
    """The same SVRG, dense step by step on ``mesh``, every array in
    ``dtype_name``: ``ids`` / ``vals`` are the ``[q*N, B]`` row stacks
    of the blocks' padded rows, split over ``axes`` (block l's rows on
    shard l), ``labels`` replicated.  ``fault``: None; ``"half_batch"``
    (the second half of each mini-batch left out, the mean taken over
    the rest); or ``"cold_start"`` (each call starts from w = 0, its
    warm start ignored).  Returns the global iterate, cut to ``dim``."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    q = int(np.prod([mesh.shape[a] for a in axes]))
    block = -(-dim // q)
    n = labels.shape[0]
    chunk = max(r for r in range(1, min(n, 16384) + 1) if n % r == 0)
    full_grad, epoch, report = _epoch_fn(
        mesh, tuple(axes), dtype_name, block, u,
        "half_batch" if fault == "half_batch" else None, chunk)
    dt = jnp.dtype(dtype_name)
    vals = vals.astype(dt)
    y = labels.astype(dt)
    zeros = NamedSharding(mesh, P(tuple(axes)))
    w = jnp.zeros((q * block,), dt, device=zeros)
    objectives, grad_norms = [], []
    for seed, outers in calls:
        if fault == "cold_start":
            w = jnp.zeros((q * block,), dt, device=zeros)
        z, s0 = full_grad(ids, vals, y, w)
        for samples in _outer_samples([(seed, outers)], n, m, u):
            w = epoch(ids, vals, y, w, z, s0, jnp.asarray(samples, jnp.int32),
                      jnp.asarray(eta, dt), jnp.asarray(lam, dt))
            z, s0 = full_grad(ids, vals, y, w)
            obj, gn = report(s0, y, w, z, jnp.asarray(lam, dt))
            objectives.append(float(obj))
            grad_norms.append(float(gn))
    return {"objectives": objectives, "grad_norms": grad_norms,
            "w": np.asarray(w, np.float64)[:dim]}
