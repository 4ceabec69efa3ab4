"""Web-page-like sparse rows (webspam trigram's shape), each feature block
made on its own chip from a seed.

The configuration's ``dim`` features are cut into q blocks of one size
(``dim`` padded up to a multiple of q, as the program's mesh does), and
chip l makes block l of every row, its ids local to the block, with no
block made on the host or on another chip:

* lengths: a row's stored ids follow the configuration's ``lengths``
  (``harness.lengths``, moved to the Table-1 total); its ids fall in the
  blocks as a multinomial draw over the blocks' real sizes.  The rows'
  (length, split) pairs are one set for every seed, in the seed's own
  order, so every seed runs the same shapes;
* ids: within block l, the block's ids in global popularity order (the
  program's multiplicative scatter of ranks, ``text.id_of_rank``) are
  drawn with P(J >= j) = (j + 1) ** -(zipf_a - 1), unique per row: the
  row keeps the first L distinct draws of a stream of i.i.d. draws (the
  same law as redrawing every collision), stored sorted by id;
* values: Gamma(2, 1) (the sum of two unit exponentials), each row
  normalized to unit L2 norm over all its blocks (one all-reduce of N
  partial sums of squares);
* labels: the sign of the margin under a planted normal teacher on the
  ``teacher_nnz_frac * dim`` most popular ids (one all-reduce of N
  partial margins), each flipped with probability ``label_noise``.

A row's draws come from keys folded from the seed, the block and the row
id alone, so the rows do not depend on how they are chunked.  Padding is
(local id 0, value 0.0); every block is ``[N, B]`` with B the widest
block share rounded up to 128 lanes.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import lengths as row_lengths
from harness import seeds

sys.path.insert(0, str(Path(__file__).resolve().parent))
from text import id_of_rank  # noqa: E402

LANES = 128
# Rows are made in classes by their widest block share, each class with
# its own stream length; the classes end at these quantiles of it.
CLASS_QUANTILES = (0.4, 0.75, 0.95, 1.0)
# Draws held at once by one chunk of rows (bounds a chunk's memory).
CHUNK_DRAWS = 1 << 24


def bounds(dim: int, q: int) -> list[int]:
    """Block bounds over ``dim`` padded up to a multiple of q."""
    size = -(-dim // q)
    return [l * size for l in range(q + 1)]


def stream_length(width: int) -> int:
    """Draws a row of at most ``width`` distinct ids is given: width **
    1.3, above every row's need at zipf_a = 1.3 (the distinct count of
    n draws grows about as n ** 0.77; ``rows`` raises if a row falls
    short)."""
    m = int(np.ceil(max(width, LANES) ** 1.3))
    return -(-m // LANES) * LANES


def split(cfg: dict, q: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(lengths int32[N], shares int32[N, q])``: each row's stored ids
    and how many fall in each block, one set for every seed in the
    seed's order."""
    n, per_row, dim = int(cfg["num_instances"]), int(cfg["nnz_per_row"]), int(cfg["dim"])
    base = np.sort(row_lengths.draw(cfg.get("lengths"), n, 0, fixed=per_row,
                                    total=n * per_row))
    b = bounds(dim, q)
    real = np.array([min(b[l + 1], dim) - b[l] for l in range(q)], np.float64)
    shares = seeds.rng(0, seeds.LENGTHS, q).multinomial(base, real / real.sum())
    order = seeds.rng(seed, seeds.LENGTHS).permutation(n)
    return base[order].astype(np.int32), shares[order].astype(np.int32)


def _tables(cfg: dict, q: int, seed: int):
    """Per block, on the host: the block's local ids in popularity order
    (padded to one length) with the count of real ones, and the block's
    slice of the teacher."""
    dim = int(cfg["dim"])
    b = bounds(dim, q)
    ids = id_of_rank(dim)
    t = max(1, int(dim * float(cfg["teacher_nnz_frac"])))
    teach = seeds.rng(seed, seeds.DATA, 1).standard_normal(t).astype(np.float32)
    size = b[1] - b[0]
    table = np.zeros((q, size), np.int32)
    teacher = np.zeros((q, size), np.float32)
    real = np.zeros(q, np.int32)
    for l in range(q):
        ranks = np.nonzero((ids >= b[l]) & (ids < b[l + 1]))[0]
        local = ids[ranks] - b[l]
        table[l, :ranks.size] = local
        real[l] = ranks.size
        hot = ranks < t
        teacher[l, local[hot]] = teach[ranks[hot]]
    return table, real, teacher


def classes(shares: np.ndarray, width: int) -> list[tuple[np.ndarray, int, int]]:
    """``(rows, W, m)`` per class: the rows whose widest block share is
    at most W (each a multiple of 128, the last ``width``), in chunks of
    rows (``rows`` is [chunks, R], padded with the row id N), given m
    draws each."""
    n = shares.shape[0]
    widest = shares.max(axis=1)
    by = np.argsort(widest, kind="stable")
    out, lo = [], 0
    for qq in CLASS_QUANTILES:
        hi = n if qq >= 1.0 else int(qq * n)
        if hi <= lo:
            continue
        w = width if qq >= 1.0 else min(width, -(-int(widest[by[hi - 1]]) // LANES) * LANES)
        m = stream_length(w)
        r = max(8, (CHUNK_DRAWS // m) // 8 * 8)
        rows = by[lo:hi].astype(np.int32)
        rows = np.concatenate([rows, np.full((-rows.size) % r, n, np.int32)])
        out.append((rows.reshape(-1, r), w, m))
        lo = hi
    return out


def _row(kd, kv, length, table, real, teacher, *, m, w, expo):
    """One row of one block: ids (sorted, padded), values, its partial
    sum of squares and teacher margin, and whether its stream held
    ``length`` distinct draws."""
    u = jax.random.uniform(kd, (m,), dtype=jnp.float32)
    j = jnp.floor(jnp.minimum(u ** expo - 1.0, (real - 1).astype(jnp.float32)))
    j = j.astype(jnp.int32)
    js, pos = jax.lax.sort((j, jnp.arange(m, dtype=jnp.int32)), num_keys=1,
                           is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), bool), js[1:] != js[:-1]])
    firsts = jnp.zeros((m,), bool).at[pos].set(first)
    cnt = jnp.cumsum(firsts.astype(jnp.int32))
    keep = firsts & (cnt <= length)
    slot = jnp.where(keep, cnt - 1, w)
    kept = jnp.zeros((w,), jnp.int32).at[slot].set(j, mode="drop")
    lane = jnp.arange(w, dtype=jnp.int32)
    live = lane < length
    ids = jnp.sort(jnp.where(live, table[kept], jnp.iinfo(jnp.int32).max))
    ids = jnp.where(live, ids, 0)
    k1, k2 = jax.random.split(kv)
    tiny = jnp.finfo(jnp.float32).tiny
    v = (-jnp.log(jax.random.uniform(k1, (w,), minval=tiny))
         - jnp.log(jax.random.uniform(k2, (w,), minval=tiny)))
    v = jnp.where(live, v, 0.0)
    return ids, v, jnp.sum(v * v), jnp.sum(teacher[ids] * v), cnt[-1] >= length


@functools.lru_cache(maxsize=4)
def _program(mesh, axes: tuple, n: int, width: int, shapes: tuple,
             expo: float, noise: float):
    """The one program every chip runs: its block of every row, then the
    two all-reduces that normalize the rows and label them."""

    def block(key_data, table, real, teacher, shares, *class_rows):
        l = jax.lax.axis_index(axes)
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        kb = jax.random.fold_in(key, l)
        kd, kv = jax.random.fold_in(kb, 0), jax.random.fold_in(kb, 1)
        table, teacher, real = table[0], teacher[0], real[0]
        carry = (jnp.zeros((n, width), jnp.int32),
                 jnp.zeros((n, width), jnp.float32),
                 jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
                 jnp.ones((), bool))
        for rows_c, (_, w, m) in zip(class_rows, shapes):
            one = functools.partial(_row, m=m, w=w, expo=expo)

            def chunk(c, carry, rows_c=rows_c, w=w, one=one):
                ids_s, vals_s, ss, mt, ok = carry
                rows = rows_c[c]
                length = jnp.take(shares, rows, mode="fill", fill_value=0)
                keys_d = jax.vmap(lambda r: jax.random.fold_in(kd, r))(rows)
                keys_v = jax.vmap(lambda r: jax.random.fold_in(kv, r))(rows)
                ids, v, s2, tm, enough = jax.vmap(
                    one, in_axes=(0, 0, 0, None, None, None))(
                    keys_d, keys_v, length, table, real, teacher)
                ids_s = ids_s.at[rows, :w].set(ids, mode="drop")
                vals_s = vals_s.at[rows, :w].set(v, mode="drop")
                ss = ss.at[rows].set(s2, mode="drop")
                mt = mt.at[rows].set(tm, mode="drop")
                return ids_s, vals_s, ss, mt, ok & jnp.all(enough)

            carry = jax.lax.fori_loop(0, rows_c.shape[0], chunk, carry)
        ids_s, vals_s, ss, mt, ok = carry
        norm = jnp.sqrt(jnp.maximum(jax.lax.psum(ss, axes), 1e-30))
        vals_s = vals_s / norm[:, None]
        margin = jax.lax.psum(mt, axes) / norm
        labels = jnp.sign(margin + 1e-12)
        flip = jax.random.uniform(jax.random.fold_in(key, q_tag), (n,)) < noise
        labels = jnp.where(flip, -labels, labels)
        labels = jnp.where(labels == 0, 1.0, labels).astype(jnp.float32)
        short = jax.lax.psum(jnp.where(ok, 0, 1), axes)
        return ids_s, vals_s, labels, short

    q_tag = 2
    split_rows = P(axes, None)
    mapped = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), split_rows, P(axes), split_rows, P(axes))
        + (P(),) * len(shapes),
        out_specs=(split_rows, split_rows, P(), P()), check_vma=False)
    return jax.jit(mapped)


def blocks(cfg: dict, seed: int, mesh, axes=("model",), shares=None):
    """The data set on ``mesh``: per block l, ``(indices, values)``
    ``[N, B]`` on the device holding shard l of the feature axes, the
    labels replicated, and the rows' lengths and block shares (host;
    ``split(cfg, q, seed)``'s unless ``shares`` int[N, q] is given)."""
    axes = tuple(axes)
    q = int(np.prod([mesh.shape[a] for a in axes]))
    if shares is None:
        lengths, shares = split(cfg, q, seed)
    else:
        shares = np.asarray(shares, np.int32)
        lengths = shares.sum(axis=1).astype(np.int32)
    n = int(shares.shape[0])
    width = -(-int(shares.max()) // LANES) * LANES
    table, real, teacher = _tables(cfg, q, seed)
    cls = classes(shares, width)
    expo = -1.0 / (float(cfg["zipf_a"]) - 1.0)
    run = _program(mesh, axes, n, width, tuple((r.shape, w, m) for r, w, m in cls),
                   expo, float(cfg["label_noise"]))
    rows_sh = NamedSharding(mesh, P(axes, None))
    vec_sh = NamedSharding(mesh, P(axes))
    repl = NamedSharding(mesh, P())
    ids, vals, labels, short = run(
        jax.device_put(seeds.words(seed, seeds.DATA, 0), repl),
        jax.device_put(table, rows_sh), jax.device_put(real, vec_sh),
        jax.device_put(teacher, rows_sh),
        jax.device_put(shares.T.reshape(-1), vec_sh),
        *(jax.device_put(r, repl) for r, _, _ in cls))
    if int(short):
        raise RuntimeError(f"{int(short)} blocks had rows whose stream held "
                           "fewer distinct ids than their length")
    per_block = sorted(zip(ids.addressable_shards, vals.addressable_shards),
                       key=lambda s: s[0].index[0].start or 0)
    return ([(i.data, v.data) for i, v in per_block], labels, lengths, shares)


def rows(cfg: dict, lengths: np.ndarray, seed: int, tag: int):
    """Device arrays ``ids int32[n, W]``, ``values`` float32: rows of
    the given lengths with unique ids, made whole as one block on the
    default device."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    shares = np.asarray(lengths, np.int32)[:, None]
    (ids, vals), = blocks(cfg, seeds.int32(seed, seeds.DATA, tag), mesh,
                          shares=shares)[0]
    return ids, vals
