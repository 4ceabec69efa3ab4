"""Everything random in a run is derived from ``--seed`` through here.

A seed is any whole number (the driver's exceed 32 bits); each consumer
names itself with a tag, so two streams of one run never share bits.
"""

from __future__ import annotations

import numpy as np


def words(seed: int, *tags: int) -> np.ndarray:
    """Two uint32 words for ``(seed, *tags)``."""
    ss = np.random.SeedSequence([int(seed) % 2**64, *(int(t) for t in tags)])
    return ss.generate_state(2, dtype=np.uint32)


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**64, *(int(t) for t in tags)])
    )


def int32(seed: int, *tags: int) -> int:
    """A non-negative 31-bit integer, for APIs that take a plain int."""
    return int(words(seed, *tags)[0] >> np.uint32(1))


def jax_key(seed: int, *tags: int):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(
        jnp.asarray(words(seed, *tags), dtype=jnp.uint32), impl="threefry2x32"
    )


# Tags: one per stream.
DATA, WEIGHTS, ORDER, ARRIVALS, LENGTHS, SOLVE = range(6)
