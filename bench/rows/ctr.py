"""Click-through rows (Avazu's shape), made on the device from a seed.

A row is one id per field, value 1.0: ``nnz_per_row`` categorical
fields, each owning ``dim // nnz_per_row`` consecutive ids of the hashed
feature space.  A field's value has popularity rank r with
P(rank >= r) = (r + 1) ** -(zipf_a - 1), scattered inside the field by a
multiplicative hash and a per-field rotation, so ids are unique within a
row by construction and skewed across rows as CTR features are.  Rows
are stored sorted by id.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import seeds


def slot_of_rank(card: int) -> np.ndarray:
    """int32[card]: a field's slot for each popularity rank."""
    mult = 2654435761 % card
    while np.gcd(mult, card) != 1:
        mult += 1
    return ((np.arange(card, dtype=np.int64) * mult) % card).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("n", "fields", "zipf_a"))
def _rows(key, table, *, n, fields, zipf_a):
    card = table.shape[0]
    u = jax.random.uniform(key, (n, fields), dtype=jnp.float32)
    raw = jnp.minimum(u ** (-1.0 / (zipf_a - 1.0)) - 1.0, float(card - 1))
    slot = table[jnp.floor(raw).astype(jnp.int32)]
    f = jnp.arange(fields, dtype=jnp.int32)
    ids = f * card + (slot + f * 7919) % card
    return jnp.sort(ids, axis=1), jnp.ones((n, fields), jnp.float32)


def rows(cfg: dict, lengths: np.ndarray, seed: int, tag: int):
    """Device arrays ``ids int32[n, fields]`` and ``values``; every row
    has one id per field, so every length must equal ``nnz_per_row``."""
    fields = int(cfg["nnz_per_row"])
    lengths = np.asarray(lengths)
    if lengths.size and (lengths.min() != fields or lengths.max() != fields):
        raise ValueError(f"CTR rows hold exactly {fields} ids each")
    table = jnp.asarray(slot_of_rank(int(cfg["dim"]) // fields))
    return _rows(seeds.jax_key(seed, seeds.DATA, tag), table,
                 n=int(lengths.size), fields=fields,
                 zipf_a=float(cfg["zipf_a"]))
