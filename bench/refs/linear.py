"""Plain reference of a linear classifier: margins, and SVRG with the
logistic loss and an L2 penalty (the paper's Algorithm 2, Option I).

It imports nothing of the program.  ``svrg`` is float64 numpy: the
dense per-step term ``z + lam * w`` is carried in closed form, so a step
costs the u sampled rows only and three outers at news20's size take a
few seconds.  ``svrg_in`` is the same algorithm written step by step in
``jax.numpy`` in a dtype of the caller's choosing: in bfloat16 it is the
control that a float32 program must be told apart from, and with a
fault switched on it stands in for a broken program.

The sample stream follows the program's documented convention: one
``numpy.random.default_rng(seed)`` per call of the solver, and per outer
``integers(0, n, size=(m, u), dtype=int64)``; Option I draws nothing
else.  A call warm-started from an iterate first takes the full
gradient there, as the end of the previous outer did.
"""

from __future__ import annotations

import functools

import numpy as np

BLOCK_ROWS = 4096


def margins(ids, vals, w) -> np.ndarray:
    """float64 margins of padded rows, in blocks of rows."""
    w = np.asarray(w, np.float64)
    out = np.empty(ids.shape[0], np.float64)
    for lo in range(0, ids.shape[0], BLOCK_ROWS):
        hi = lo + BLOCK_ROWS
        out[lo:hi] = np.sum(w[ids[lo:hi]] * np.asarray(vals[lo:hi], np.float64), axis=1)
    return out


def magnitudes(ids, vals, w) -> np.ndarray:
    """sum_k |w[id_k] * val_k| per row: the scale a margin's rounding
    error is measured against."""
    w = np.abs(np.asarray(w, np.float64))
    out = np.empty(ids.shape[0], np.float64)
    for lo in range(0, ids.shape[0], BLOCK_ROWS):
        hi = lo + BLOCK_ROWS
        out[lo:hi] = np.sum(w[ids[lo:hi]] * np.abs(np.asarray(vals[lo:hi], np.float64)), axis=1)
    return out


def _dlogistic(s, y):
    # d/ds log(1 + exp(-y s)) = -y / (1 + exp(y s))
    return -y / (1.0 + np.exp(y * s))


def _objective(s, y, w, lam):
    return float(np.mean(np.logaddexp(0.0, -y * s)) + 0.5 * lam * np.dot(w, w))


def _full_grad(ids, vals, y, w, dim):
    s = margins(ids, vals, w)
    coef = _dlogistic(s, y) / y.shape[0]
    z = np.bincount(ids.ravel(), weights=(vals * coef[:, None]).ravel(),
                    minlength=dim)
    return z, s


def _outer_samples(calls, n, m, u):
    """Each outer's (m, u) samples: per call ``(seed, outers)`` one
    generator of that seed, one draw per outer."""
    for seed, outers in calls:
        rng = np.random.default_rng(seed)
        for _ in range(outers):
            yield rng.integers(0, n, size=(m, u), dtype=np.int64)


def svrg(ids, vals, labels, *, dim, lam, eta, u, m, calls) -> dict:
    """float64 SVRG from w = 0 through ``calls``, a list of ``(seed,
    outers)``, each call warm-started from the last one's iterate: the
    objective after each outer, the gradient norm after the first, and
    the final iterate."""
    ids = np.asarray(ids)
    vals = np.asarray(vals, np.float64)
    y = np.asarray(labels, np.float64)
    n = ids.shape[0]
    c = 1.0 - eta * lam
    w = np.zeros(dim)
    z, s0 = _full_grad(ids, vals, y, w, dim)
    objectives, grad_norms = [], []
    for samples in _outer_samples(calls, n, m, u):
        # w_k = c**k * v + a * z, with v and a updated per step: the
        # dense part of each step is the two scalars.
        v, a = w.copy(), 0.0
        for k in range(m):
            rows = samples[k]
            idx, val, yk = ids[rows], vals[rows], y[rows]
            wk = c**k * v[idx] + a * z[idx]
            s = np.sum(wk * val, axis=1)
            coef = (_dlogistic(s, yk) - _dlogistic(s0[rows], yk)) / u
            np.add.at(v, idx.ravel(), (-eta / c ** (k + 1)) * (val * coef[:, None]).ravel())
            a = c * a - eta
        w = c**m * v + a * z
        z, s0 = _full_grad(ids, vals, y, w, dim)
        objectives.append(_objective(s0, y, w, lam))
        grad_norms.append(float(np.linalg.norm(z + lam * w)))
    return {"objectives": objectives, "grad_norms": grad_norms, "w": w}


@functools.lru_cache(maxsize=None)
def _epoch_fn(dtype_name: str, dim: int, u: int, fault: str | None):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    used = u // 2 if fault == "half_batch" else u

    def dphi(s, y):
        return -y * jax.nn.sigmoid(-y * s)

    @jax.jit
    def full_grad(ids, vals, y, w):
        s = jnp.sum(w[ids] * vals, axis=1)
        coef = (dphi(s, y) / y.shape[0]).astype(dt)
        z = jnp.zeros((dim,), dt).at[ids.ravel()].add((vals * coef[:, None]).ravel())
        return z, s

    @jax.jit
    def epoch(ids, vals, y, w, z, s0, samples, eta, lam):
        def step(w, rows):
            rows = rows[:used]
            idx, val, yk = ids[rows], vals[rows], y[rows]
            s = jnp.sum(w[idx] * val, axis=1)
            coef = ((dphi(s, yk) - dphi(s0[rows], yk)) / used).astype(dt)
            g = jnp.zeros((dim,), dt).at[idx.ravel()].add((val * coef[:, None]).ravel())
            return (w - eta * (g + z + lam * w)).astype(dt), None

        w, _ = jax.lax.scan(step, w, samples)
        return w

    @jax.jit
    def report(s, y, w, z, lam):
        obj = jnp.mean(jnp.logaddexp(0.0, -y * s)) + 0.5 * lam * jnp.sum(w * w)
        return obj, jnp.linalg.norm(z + lam * w)

    return full_grad, epoch, report


def svrg_in(dtype_name, ids, vals, labels, *, dim, lam, eta, u, m, calls,
            fault: str | None = None) -> dict:
    """The same SVRG, dense step by step on the default device, every
    array in ``dtype_name``.  ``fault``: None; ``"half_batch"`` (the
    second half of each mini-batch left out, the mean taken over the
    rest); or ``"cold_start"`` (each call starts from w = 0, its warm
    start ignored)."""
    import jax.numpy as jnp

    full_grad, epoch, report = _epoch_fn(dtype_name, dim, u,
                                         "half_batch" if fault == "half_batch" else None)
    dt = jnp.dtype(dtype_name)
    ids = jnp.asarray(ids)
    vals = jnp.asarray(vals).astype(dt)
    y = jnp.asarray(labels).astype(dt)
    w = jnp.zeros((dim,), dt)
    objectives, grad_norms = [], []
    for seed, outers in calls:
        if fault == "cold_start":
            w = jnp.zeros((dim,), dt)
        z, s0 = full_grad(ids, vals, y, w)
        for samples in _outer_samples([(seed, outers)], ids.shape[0], m, u):
            w = epoch(ids, vals, y, w, z, s0, jnp.asarray(samples, jnp.int32),
                      jnp.asarray(eta, dt), jnp.asarray(lam, dt))
            z, s0 = full_grad(ids, vals, y, w)
            obj, gn = report(s0, y, w, z, jnp.asarray(lam, dt))
            objectives.append(float(obj))
            grad_norms.append(float(gn))
    return {"objectives": objectives, "grad_norms": grad_norms,
            "w": np.asarray(w, np.float64)}


def margins_in(dtype_name, ids, vals, w) -> np.ndarray:
    """Margins with ids, values and w rounded to ``dtype_name`` and the
    products summed in it, on the default device (the serving control)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    f = jax.jit(lambda i, v, w: jnp.sum(w[i] * v, axis=1, dtype=dt))
    wd = jnp.asarray(w).astype(dt)
    out = [np.asarray(f(jnp.asarray(ids[lo:lo + BLOCK_ROWS]),
                        jnp.asarray(vals[lo:lo + BLOCK_ROWS]).astype(dt), wd),
                      np.float64)
           for lo in range(0, ids.shape[0], BLOCK_ROWS)]
    return np.concatenate(out) if out else np.zeros(0)
