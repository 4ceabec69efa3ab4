"""Padded-CSR sparse matrices for high-dimensional (d >> N) data in JAX.

The paper's data sets (news20, url, webspam, kdd2010) are extremely sparse
text/web feature matrices with d up to 29.9M.  TPUs (and XLA generally)
want static shapes, so we store each instance with a fixed nnz budget:

    indices: int32[N, nnz_max]   feature ids, padded with 0
    values:  float32[N, nnz_max] feature values, padded with 0.0

Padding with (index 0, value 0.0) is safe for every operation used here
(dots and scatter-adds), because a zero value contributes nothing.

The feature-distributed view of the same matrix lives in
:mod:`repro.data.block_csr`: per-block re-indexed padded rows with a
per-block nnz budget, so a worker's gather/scatter work is O(nnz_max/q)
against local ids with zero masking arithmetic.  (The historical
masked-global view — keep global ids everywhere and select ids in
[lo, hi) with ``(idx >= lo) & (idx < hi)`` on every access — cost every
worker the full O(nnz_max) per row and survives only as the oracle the
BlockCSR property tests compare against.)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PaddedCSR:
    """A sparse d x N design matrix stored instance-major with padded rows."""

    indices: jax.Array  # int32[N, nnz_max]
    values: jax.Array  # float32[N, nnz_max]
    labels: jax.Array  # float32[N], in {-1, +1}
    dim: int  # d

    @property
    def num_instances(self) -> int:
        return int(self.indices.shape[0])

    @property
    def nnz_max(self) -> int:
        return int(self.indices.shape[1])

    def nnz_total(self) -> int:
        return int(jnp.sum(self.values != 0.0))

    def instance(self, i: int) -> tuple[jax.Array, jax.Array]:
        return self.indices[i], self.values[i]

    def to_dense(self) -> np.ndarray:
        """Dense d x N matrix (tests / tiny data only)."""
        n, nnz = self.indices.shape
        out = np.zeros((self.dim, n), dtype=np.float32)
        idx = np.asarray(self.indices).reshape(-1)
        val = np.asarray(self.values, dtype=np.float32).reshape(-1)
        cols = np.repeat(np.arange(n), nnz)
        # np.add.at handles repeated indices (padding collides on 0).
        np.add.at(out, (idx, cols), val)
        return out


def margins_rows(
    indices: jax.Array, values: jax.Array, w: jax.Array
) -> jax.Array:
    """s_i = w^T x_i from padded rows; the one definition of the margin
    gather every global-layout path shares (objective, full gradient,
    serial inner loop)."""
    with jax.named_scope("margins_rows"):
        return jnp.sum(w[indices] * values, axis=-1)


def margins(data: PaddedCSR, w: jax.Array) -> jax.Array:
    """s_i = w^T x_i for all instances; w is the dense d-vector."""
    return margins_rows(data.indices, data.values, w)


def scatter_grad(
    indices: jax.Array,
    values: jax.Array,
    coeffs: jax.Array,
    dim: int,
) -> jax.Array:
    """sum_i coeffs_i * x_i as a dense d-vector (the data-dependent gradient).

    indices/values: [N, nnz]; coeffs: [N].
    """
    flat_idx = indices.reshape(-1)
    flat_val = (values * coeffs[:, None]).reshape(-1)
    return jnp.zeros((dim,), dtype=values.dtype).at[flat_idx].add(flat_val)
