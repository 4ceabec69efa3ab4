"""Pallas TPU kernel: fused scatter-grad + proximal variance-reduced update.

FD-Prox-SVRG inner step (Algorithm 1 line 11 + the block-local prox),
per worker and per inner step:

    g^(l)  = sum_i coef_i * x^(l)_i                       (local scatter)
    v^(l)  = w^(l) - eta * (g^(l) + z^(l) + lam * w^(l))  (smooth part)
    w^(l)' = prox_{eta*g_ns}(v^(l))                       (block-local prox)

with ``lam`` the smooth L2 coefficient (the classic path), ``lam1`` the
L1 strength and ``lam2`` the elastic-net L2 strength handled in closed
form: soft-threshold by ``eta*lam1`` then shrink by ``1/(1+eta*lam2)``.
Because g decomposes over feature blocks (paper eq. 3) the prox is
elementwise — it fuses into the same pass as the scatter and the update,
and costs zero extra communication.

Layout.  ``w``/``z`` arrive lane-major (``[rows, 128]``, feature ``j`` at
``(j // 128, j % 128)``) and the grid walks chunks of rows, so VMEM holds
one chunk of each at a time whatever ``d_block`` is.  The mini-batch's
ids, values and coefficients sit in SMEM.  Mosaic has no vector
scatter-add, so on the TPU the scatter is a scalar loop
(``scalar_loops=True``) over the ``u * nnz_l`` entries in row-major
order — the order of the reference's ``.at[idx].add`` — and each entry
that falls in the chunk does one read-modify-write of its 128-lane row
with ``-0.0`` (the identity of addition) on the other lanes.  The
gradient accumulates on top of its dense part ``z + lam * w``, the
association XLA itself rewrites the reference's
``scatter(0, ...) + z + lam * w`` into; the output chunk is the
accumulator until the update overwrites it.

``lam``/``lam1``/``lam2`` are compile-time constants of the run; ``eta``
arrives as a runtime scalar because Option II masks the step size per
inner step.  When ``lam1 == lam2 == 0`` the prox stages are elided at
trace time, leaving exactly the expression tree of
:mod:`repro.kernels.fused_update` — so the L2 family keeps its historical
bit-identity, and one kernel covers the whole regularizer family.

``scalar_loops=False`` scatters with ``.at[idx].add`` and runs only
under the interpreter (CPU), where it is the numerics contract: scatter,
update, and prox are computed with exactly the reference's jnp
expression tree (``sign(v) * max(|v| - eta*lam1, 0)``, then the division
only when ``lam2 != 0``), so the ``use_kernels`` path is bit-identical to
the reference path for every regularizer.  The loop body adds the same
terms, but XLA:CPU applies its reassociation of the reference by
context, so there the tests hold it to float32 rounding instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _scatter_onto(idx_ref, val_ref, coef_ref, out_ref):
    """out[idx[i, k]] += val[i, k] * coef[i] for the ids in this chunk,
    one entry at a time in row-major order."""
    rows = out_ref.shape[0]
    first_row = pl.program_id(0) * rows
    u, nnz = idx_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def scatter_entry(e, carry):
        i = e // nnz
        k = e % nnz
        j = idx_ref[i, k]
        r = j // LANES - first_row

        @pl.when((r >= 0) & (r < rows))
        def _():
            contrib = val_ref[i, k] * coef_ref[i]
            out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] + jnp.where(
                lane == j % LANES, contrib, -0.0
            )

        return carry

    jax.lax.fori_loop(0, u * nnz, scatter_entry, 0)


def _prox_update_kernel(lam: float, lam1: float, lam2: float,
                        scalar_loops: bool, idx_ref, val_ref, coef_ref,
                        eta_ref, w_ref, z_ref, out_ref):
    w = w_ref[...]
    eta = eta_ref[0]
    if scalar_loops:
        out_ref[...] = z_ref[...] + lam * w
        _scatter_onto(idx_ref, val_ref, coef_ref, out_ref)
        v = w - eta * out_ref[...]
    else:
        w = w.reshape(-1)
        contrib = val_ref[...] * coef_ref[...][:, None]
        g = (
            jnp.zeros_like(w)
            .at[idx_ref[...].reshape(-1)]
            .add(contrib.reshape(-1))
        )
        v = w - eta * (g + z_ref[...].reshape(-1) + lam * w)
    if lam1 != 0.0 or lam2 != 0.0:
        # losses.soft_threshold, verbatim — the shared numerics contract.
        v = jnp.sign(v) * jnp.maximum(jnp.abs(v) - eta * lam1, 0.0)
        if lam2 != 0.0:
            v = v / (1.0 + eta * lam2)
    out_ref[...] = v.reshape(out_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_rows", "lam", "lam1", "lam2", "scalar_loops", "interpret",
    ),
)
def prox_update(
    w: jax.Array,  # [w_rows, 128] lane-major w block
    indices: jax.Array,  # int32[u, nnz_l], local ids
    values: jax.Array,  # [u, nnz_l]
    coef: jax.Array,  # [u]
    z: jax.Array,  # [w_rows, 128] lane-major z block
    eta: jax.Array,  # [1] runtime step size (eta * option mask)
    *,
    chunk_rows: int,
    lam: float,
    lam1: float,
    lam2: float,
    scalar_loops: bool = True,
    interpret: bool = False,
) -> jax.Array:  # [w_rows, 128]
    w_rows, lanes = w.shape
    assert lanes == LANES and z.shape == w.shape
    assert w_rows % chunk_rows == 0, "caller pads rows to chunk multiples"
    u, nnz = indices.shape
    assert values.shape == (u, nnz) and coef.shape == (u,)
    assert eta.shape == (1,)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    chunk = pl.BlockSpec((chunk_rows, LANES), lambda c: (c, 0))
    return pl.pallas_call(
        functools.partial(_prox_update_kernel, lam, lam1, lam2, scalar_loops),
        grid=(w_rows // chunk_rows,),
        in_specs=[smem, smem, smem, smem, chunk, chunk],
        out_specs=chunk,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
    )(indices, values, coef, eta, w, z)
