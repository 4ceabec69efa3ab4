"""Text-like sparse rows (news20's shape), made on the device from a seed.

A copy of the program's ``make_sparse_classification`` with one change:
the ids of a row are unique, by redrawing every collision from the same
popularity law until none is left, so a row's values, normalized to unit
L2 norm, make a unit-norm row as LibSVM's text sets are.  (The program's
generator nudges collisions twice and leaves repeats, which merge into
rows of squared norm 15-22.)

* ids: popularity rank r with P(rank >= r) = (r + 1) ** -(zipf_a - 1)
  (inverse CDF of a continuous Pareto), clamped to [0, dim), scattered
  over the id space by ``(r * mult + 12345) % dim`` as the program does;
* values: Gamma(2, 1), normalized per row over its stored entries;
* labels: the sign of the margin under a planted normal teacher on the
  ``teacher_nnz_frac * dim`` most popular ids, each flipped with
  probability ``label_noise``.

Rows are stored sorted by id (as LibSVM stores them), padded with
(id 0, value 0.0) past their length to the longest row's width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import lengths as row_lengths
from harness import seeds

MAX_ROUNDS = 2000


def id_of_rank(dim: int) -> np.ndarray:
    """int32[dim]: the id of each popularity rank (the program's
    multiplicative scatter)."""
    mult = 2654435761 % dim
    return ((np.arange(dim, dtype=np.int64) * mult + 12345) % dim).astype(np.int32)


def _ranks(key, shape, dim, zipf_a):
    u = jax.random.uniform(key, shape, dtype=jnp.float32)
    raw = jnp.minimum(u ** (-1.0 / (zipf_a - 1.0)) - 1.0, float(dim - 1))
    return jnp.floor(raw).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dim", "width", "zipf_a"))
def _unique_rows(key, lengths, perm, *, dim, width, zipf_a):
    n = lengths.shape[0]
    pos = jnp.arange(width, dtype=jnp.int32)
    active = pos[None, :] < lengths[:, None]
    # Past its length a row holds distinct sentinels above every id, so
    # they sort last and never collide.
    sentinel = jnp.broadcast_to(dim + pos, (n, width))
    k0, kv, _ = jax.random.split(key, 3)
    ids = jnp.where(active, perm[_ranks(k0, (n, width), dim, zipf_a)], sentinel)

    def cond(c):
        return (c[3] > 0) & (c[2] < MAX_ROUNDS)

    def body(c):
        ids, k, rounds, _ = c
        s = jnp.sort(ids, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((n, 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
        k, sub = jax.random.split(k)
        fresh = perm[_ranks(sub, (n, width), dim, zipf_a)]
        return jnp.where(dup, fresh, s), k, rounds + 1, jnp.sum(dup)

    ids, _, rounds, left = jax.lax.while_loop(
        cond, body, (ids, k0, jnp.int32(0), jnp.int32(1)))
    vals = jnp.where(active, jax.random.gamma(kv, 2.0, (n, width)), 0.0)
    vals = vals / jnp.maximum(
        jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True)), 1e-8)
    ids = jnp.where(active, ids, 0)
    return ids, vals.astype(jnp.float32), rounds, left


@functools.partial(jax.jit, static_argnames=("dim", "teacher_nnz", "label_noise"))
def _labels(key, ids, vals, perm, *, dim, teacher_nnz, label_noise):
    kt, kf = jax.random.split(key)
    teacher = jnp.zeros((dim,), jnp.float32).at[perm[:teacher_nnz]].set(
        jax.random.normal(kt, (teacher_nnz,), jnp.float32))
    margins = jnp.sum(vals * teacher[ids], axis=1)
    labels = jnp.sign(margins + 1e-12)
    flip = jax.random.uniform(kf, labels.shape) < label_noise
    labels = jnp.where(flip, -labels, labels)
    return jnp.where(labels == 0, 1.0, labels).astype(jnp.float32)


def rows(cfg: dict, lengths: np.ndarray, seed: int, tag: int):
    """Device arrays ``ids int32[n, max(lengths)]``, ``values`` float32,
    rows of the given lengths with unique ids."""
    dim = int(cfg["dim"])
    lengths = np.asarray(lengths, np.int32)
    ids, vals, rounds, left = _unique_rows(
        seeds.jax_key(seed, seeds.DATA, tag), jnp.asarray(lengths),
        jnp.asarray(id_of_rank(dim)), dim=dim, width=int(lengths.max()),
        zipf_a=float(cfg["zipf_a"]))
    if int(left):
        raise RuntimeError(
            f"{int(left)} repeated ids left after {int(rounds)} rounds")
    return ids, vals


def dataset(cfg: dict, seed: int):
    """The training set: ``num_instances`` rows of unique ids, their
    lengths drawn by the configuration's ``lengths`` to sum to
    ``num_instances * nnz_per_row`` stored entries, and their labels
    (device arrays)."""
    n = int(cfg["num_instances"])
    per_row = int(cfg["nnz_per_row"])
    lens = row_lengths.draw(cfg.get("lengths"), n, seed, fixed=per_row,
                            total=n * per_row)
    ids, vals = rows(cfg, lens, seed, 0)
    dim = int(cfg["dim"])
    labels = _labels(
        seeds.jax_key(seed, seeds.DATA, 1), ids, vals,
        jnp.asarray(id_of_rank(dim)), dim=dim,
        teacher_nnz=max(1, int(dim * float(cfg["teacher_nnz_frac"]))),
        label_noise=float(cfg["label_noise"]))
    return ids, vals, labels
