"""The full gradient over row-length groups (``BlockCSR.groups``).

The full gradient walks each block's rows grouped by length class, each
group padded to its own multiple of 128 lanes, instead of the padded
``[N, nnz_l]`` slab.  These tests hold the grouped view to its
invariants on ragged rows and the grouped ``_full_grad_blocks`` to the
padded computation it replaced: within float32 rounding on ragged rows,
bit for bit where every row falls in one class.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses
from repro.core.fdsvrg import (
    SVRGConfig,
    _bounds,
    _full_grad_blocks,
    fdsvrg_worker_simulation,
    run_fdsvrg,
)
from repro.core.partition import balanced
from repro.data.block_csr import GROUP_LANES, BlockCSR, block_margins, local_scatter
from repro.data.pipeline import ArraySource, stream_block_csr
from repro.data.sparse import PaddedCSR
from repro.dist import SimBackend, tree_order_sum
from repro.optim.update_rules import SVRGRule, _full_grad_lanes, make_context

WIDTH = 1024  # the padded row width, as in the news20 benchmark


def _ragged(n=64, dim=8192, seed=0, uniform=None, explicit_zeros=0.05):
    """Unit-norm rows of lognormal length (1-1,000 ids, unique per row),
    padded to WIDTH lanes; a share of stored values set to an explicit
    0.0 mid-row.  ``uniform``: every row holds that many ids instead, in
    rows exactly that wide."""
    rng = np.random.default_rng(seed)
    if uniform is None:
        lengths = np.clip(
            np.round(200 * np.exp(0.8 * rng.standard_normal(n))), 1, 1000
        ).astype(int)
    else:
        lengths = np.full(n, uniform)
    width = WIDTH if uniform is None else uniform
    idx = np.zeros((n, width), np.int32)
    val = np.zeros((n, width), np.float32)
    for i, k in enumerate(lengths):
        idx[i, :k] = np.sort(rng.choice(dim, size=k, replace=False))
        v = rng.gamma(2.0, 1.0, size=k).astype(np.float32)
        val[i, :k] = v / np.linalg.norm(v)
    if explicit_zeros:
        val[(rng.random(val.shape) < explicit_zeros) & (val != 0)] = 0.0
    labels = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    return PaddedCSR(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels),
                     dim)


def _layout(data, q):
    return BlockCSR.from_padded(
        data, balanced(data.dim, q), lane_multiple=GROUP_LANES
    )


@functools.partial(jax.jit, static_argnames=("block_dims", "use_kernels"))
def _padded_full_grad(block_indices, block_values, labels, w, block_dims,
                      use_kernels):
    """The full gradient over the padded slabs, as it ran before the
    row groups: every lane of every row gathered and scattered."""
    bounds = _bounds(block_dims)
    s0 = tree_order_sum([
        block_margins(block_indices[l], block_values[l],
                       w[bounds[l]:bounds[l + 1]], use_kernels)
        for l in range(len(block_dims))
    ])
    coeffs = losses.logistic.dvalue(s0, labels) / labels.shape[0]
    z = [local_scatter(block_indices[l], block_values[l], coeffs, d)
         for l, d in enumerate(block_dims)]
    return jnp.concatenate(z) if len(z) > 1 else z[0], s0


def _w(dim, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=dim).astype(np.float32)
    )


@pytest.mark.parametrize("q", [1, 2, 4])
def test_every_stored_entry_is_in_exactly_one_group(q):
    bd = _layout(_ragged(), q)
    for l, g in enumerate(bd.groups):
        rows = np.concatenate([np.asarray(r) for r in g.rows])
        # Every row in exactly one group; order puts them back.
        np.testing.assert_array_equal(np.sort(rows), np.arange(bd.num_instances))
        np.testing.assert_array_equal(rows[np.asarray(g.order)],
                                      np.arange(bd.num_instances))
        got = []
        for idx, val, r in zip(g.indices, g.values, g.rows):
            idx, val, r = np.asarray(idx), np.asarray(val), np.asarray(r)
            assert idx.shape[1] % GROUP_LANES == 0
            assert idx.shape[1] <= bd.nnz_budgets[l]
            nz = np.nonzero(val)
            got += zip(r[nz[0]], idx[nz], val[nz])
        slab_idx, slab_val = (np.asarray(a) for a in bd.block(l))
        nz = np.nonzero(slab_val)
        want = list(zip(nz[0], slab_idx[nz], slab_val[nz]))
        assert sorted(got) == sorted(want)
    assert len(bd.groups[0].indices) > 1  # the rows are ragged


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("q", [1, 2, 4])
def test_grouped_full_grad_matches_padded(q, use_kernels):
    data = _ragged()
    bd = _layout(data, q)
    w = _w(data.dim)
    z, s0 = _full_grad_blocks(bd.groups, bd.labels, w, "logistic",
                              bd.block_dims, use_kernels)
    z_ref, s0_ref = _padded_full_grad(bd.indices, bd.values, bd.labels, w,
                                      bd.block_dims, use_kernels)
    # s0 in the source's row order, as the inner epoch's s0[ids] reads it.
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s0_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref),
                               rtol=1e-5, atol=1e-7)
    lanes, _ = _full_grad_lanes(bd)
    assert lanes < bd.num_instances * sum(bd.nnz_budgets)


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("q", [1, 2, 4])
def test_uniform_rows_keep_one_group_bit_for_bit(q, use_kernels):
    """Rows of 300 ids in 300 lanes, with tight per-block budgets: each
    block's rows fall in one class (q = 2: 150 +- 9 ids in one class of
    the block's budget; q = 4: 75 +- 7, under 128), so its one group is
    the slab itself, and the computation the padded one, bit for bit."""
    data = _ragged(uniform=300, explicit_zeros=0.0)
    bd = BlockCSR.from_padded(data, balanced(data.dim, q))
    for g, idx in zip(bd.groups, bd.indices):
        assert len(g.indices) == 1
        assert g.indices[0] is idx
        np.testing.assert_array_equal(np.asarray(g.order),
                                      np.arange(bd.num_instances))
    w = _w(data.dim)
    z, s0 = _full_grad_blocks(bd.groups, bd.labels, w, "logistic",
                              bd.block_dims, use_kernels)
    z_ref, s0_ref = _padded_full_grad(bd.indices, bd.values, bd.labels, w,
                                      bd.block_dims, use_kernels)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s0_ref))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(z_ref))


@pytest.mark.parametrize("q", [1, 2, 4])
def test_streamed_layout_builds_the_same_groups(q):
    data = _ragged()
    part = balanced(data.dim, q)
    one = BlockCSR.from_padded(data, part, lane_multiple=GROUP_LANES)
    streamed = stream_block_csr(ArraySource(data), part, chunk_rows=24,
                                lane_multiple=GROUP_LANES)
    for a, b in zip(one.groups, streamed.groups):
        leaves_a, tree_a = jax.tree_util.tree_flatten(a)
        leaves_b, tree_b = jax.tree_util.tree_flatten(b)
        assert tree_a == tree_b
        for x, y in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("k", [1, 2])
def test_multi_output_snapshot_over_groups(k):
    """The k > 1 path vmaps the grouped full gradient over the output
    columns; each column equals the padded computation."""
    data = _ragged()
    bd = _layout(data, 2)
    rng = np.random.default_rng(3)
    labels = jnp.asarray(np.where(rng.random((bd.num_instances, k)) < 0.5,
                                  -1.0, 1.0).astype(np.float32))
    bd = BlockCSR(partition=bd.partition, indices=bd.indices,
                  values=bd.values, labels=labels, dim=bd.dim,
                  nnz_col=bd.nnz_col, nnz_max=bd.nnz_max, stored=bd.stored,
                  groups=bd.groups)
    cfg = SVRGConfig(eta=0.1, inner_steps=4, outer_iters=1)
    ctx = make_context(bd, losses.logistic, losses.l2(1e-3), cfg)
    w = jnp.stack([_w(data.dim, seed=j) for j in range(k)], axis=1)
    z, s0 = SVRGRule().build_snapshot(ctx)(w[:, 0] if k == 1 else w)
    z, s0 = np.asarray(z).reshape(data.dim, k), np.asarray(s0).reshape(-1, k)
    for j in range(k):
        z_ref, s0_ref = _padded_full_grad(bd.indices, bd.values,
                                          labels[:, j], w[:, j],
                                          bd.block_dims, False)
        np.testing.assert_allclose(s0[:, j], np.asarray(s0_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(z[:, j], np.asarray(z_ref),
                                   rtol=1e-5, atol=1e-7)


def test_worker_simulation_reads_the_same_groups():
    """The object-level simulation snapshots over the same row groups:
    on ragged rows it follows run_fdsvrg at the suite's simulation bar."""
    data = _ragged(n=48)
    q = 2
    part = balanced(data.dim, q)
    bd = _layout(data, q)
    assert len(bd.groups[0].indices) > 1
    cfg = SVRGConfig(eta=0.5, inner_steps=6, outer_iters=2, batch_size=2,
                     seed=4)
    reg = losses.l2(1e-3)
    ref = run_fdsvrg(None, part, losses.logistic, reg, cfg,
                     backend=SimBackend(q), block_data=bd)
    sim = fdsvrg_worker_simulation(None, part, losses.logistic, reg, cfg,
                                   backend=SimBackend(q), block_data=bd)
    np.testing.assert_allclose(np.asarray(sim.w), np.asarray(ref.w),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose([h.objective for h in sim.history],
                               [h.objective for h in ref.history], rtol=1e-5)
