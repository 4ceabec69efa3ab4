"""Pallas TPU kernel: fused sparse gather-margin over block-local CSR rows.

The FD-SVRG hot path (Algorithm 1 lines 4 and 9) is, per worker,

    s^(l)_i = sum_k w^(l)[idx[i, k]] * val[i, k]

over block-LOCAL padded rows (:class:`repro.data.block_csr.BlockCSR`) —
no membership mask, no id rebasing.

Layout.  The w block stays resident in VMEM across the row grid as a
lane-major ``[rows, 128]`` matrix (feature ``j`` lives at
``(j // 128, j % 128)``; the wrapper pads ``d_block`` up to it).  Mosaic
has no vector gather across a VMEM array, so on the TPU the gather is a
scalar loop (``scalar_loops=True``): the row tile's ids arrive in SMEM,
and each id drives one dynamic sublane load of its 128-lane row of ``w``
plus a masked lane reduction that isolates ``w[j]`` into lane ``k`` of
the gathered row.  The masked lanes are filled with ``-0.0``, the
identity of floating-point addition, so the reduction returns ``w[j]``
bit-exactly (sign of zero included).  The gathered ``[rows, nnz_l]``
tile then goes through exactly the reference's
``sum(gathered * val, axis=-1)``.

``scalar_loops=False`` gathers with jnp indexing instead; that body only
runs under the interpreter, where it is the numerics contract: margins
are bit-identical to :func:`repro.kernels.ref.sparse_margin_ref`.  The
loop body cannot promise that on CPU: XLA:CPU picks a row reduction's
vectorization and multiply-add contraction by what else sits in its
fusion, so a tile gathered by the loop and then reduced can round
differently (by an ulp) from the reference's fused gather-reduce.  Its
gather is exact, and the tests check it under the interpreter too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _gather_tile(idx_ref, w_ref, g_ref):
    """g[i, k] = w[idx[i, k]] for one row tile, one id at a time."""
    rows, width = g_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def gather_row(i, carry):
        def gather_entry(k, g_row):
            j = idx_ref[i, k]
            w_row = w_ref[pl.ds(j // LANES, 1), :]
            w_j = jnp.sum(
                jnp.where(lane == j % LANES, w_row, -0.0), axis=1, keepdims=True
            )
            return jnp.where(col == k, w_j, g_row)

        g_ref[pl.ds(i, 1), :] = jax.lax.fori_loop(
            0, width, gather_entry, jnp.zeros((1, width), g_ref.dtype)
        )
        return carry

    jax.lax.fori_loop(0, rows, gather_row, 0)


def _sparse_margin_kernel(scalar_loops, idx_ref, w_ref, val_ref, out_ref,
                          g_ref):
    """One row tile: out[rows] = sum_k w[idx[rows, k]] * val[rows, k]."""
    if scalar_loops:
        _gather_tile(idx_ref, w_ref, g_ref)
        gathered = g_ref[...]
    else:
        gathered = w_ref[...].reshape(-1)[idx_ref[...]]
    out_ref[...] = jnp.sum(gathered * val_ref[...], axis=-1)[None, :]


@functools.partial(
    jax.jit, static_argnames=("block_rows", "scalar_loops", "interpret")
)
def sparse_margin(
    w: jax.Array,  # [w_rows, 128] lane-major w block
    indices: jax.Array,  # int32[N, nnz_l], local ids
    values: jax.Array,  # [N, nnz_l]
    *,
    block_rows: int,
    scalar_loops: bool = True,
    interpret: bool = False,
) -> jax.Array:  # [N]
    w_rows, lanes = w.shape
    assert lanes == LANES, "w must be lane-major [rows, 128]"
    n, nnz = indices.shape
    assert values.shape == (n, nnz), f"{values.shape} vs {indices.shape}"
    assert n % block_rows == 0, "caller pads rows to tile multiples"

    tiles = n // block_rows
    row_spec = lambda space: pl.BlockSpec(
        (block_rows, nnz), lambda t: (t, 0), memory_space=space
    )
    out = pl.pallas_call(
        functools.partial(_sparse_margin_kernel, scalar_loops),
        grid=(tiles,),
        in_specs=[
            row_spec(pltpu.SMEM),
            pl.BlockSpec((w_rows, LANES), lambda t: (0, 0)),
            row_spec(pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (pl.Squeezed(), 1, block_rows), lambda t: (t, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (tiles, 1, block_rows), jnp.result_type(w, values)
        ),
        scratch_shapes=[pltpu.VMEM((block_rows, nnz), w.dtype)],
        interpret=interpret,
    )(indices, w, values)
    return out.reshape(n)
