"""BlockCSR: the block-local sharded layout must match the masked
global-CSR computation exactly, for any partition.

The masked path — keep global ids, select ids in [lo, hi) with
``(idx >= lo) & (idx < hi)`` on every access — is re-implemented inline
here as the oracle; it no longer exists in the library because BlockCSR
replaced it on every hot path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partition import balanced, by_nnz, feature_counts
from repro.data.block_csr import BlockCSR, local_margins, local_scatter
from repro.data.sparse import PaddedCSR, margins, scatter_grad
from repro.data.synthetic import make_sparse_classification

try:
    import hypothesis  # noqa: F401  (dev-only dep; see requirements-dev.txt)

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


RNG = np.random.default_rng(0)


def _data(dim=517, n=41, nnz=11, seed=0):
    return make_sparse_classification(
        dim=dim, num_instances=n, nnz_per_instance=nnz, seed=seed
    )


# ---------------------------------------------------------------------------
# masked global-CSR oracle (the pattern BlockCSR killed)
# ---------------------------------------------------------------------------


def masked_margins(indices, values, w_block, lo):
    hi = lo + w_block.shape[0]
    in_block = (indices >= lo) & (indices < hi)
    local = jnp.where(in_block, indices - lo, 0)
    gathered = jnp.where(in_block, w_block[local], 0.0)
    return jnp.sum(gathered * values, axis=-1)


def masked_scatter(indices, values, coeffs, lo, block_dim):
    hi = lo + block_dim
    in_block = (indices >= lo) & (indices < hi)
    local = jnp.where(in_block, indices - lo, 0)
    contrib = jnp.where(in_block, values, 0.0) * coeffs[..., None]
    return (
        jnp.zeros((block_dim,), dtype=values.dtype)
        .at[local.reshape(-1)]
        .add(contrib.reshape(-1))
    )


def _random_partition(rng, dim, q):
    cuts = np.sort(rng.choice(np.arange(1, dim), size=q - 1, replace=False))
    from repro.core.partition import FeaturePartition

    return FeaturePartition(dim=dim, bounds=(0, *map(int, cuts), dim))


# ---------------------------------------------------------------------------
# layout construction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [1, 2, 3, 5, 8])
def test_from_padded_budgets_and_coverage(q):
    data = _data()
    part = balanced(data.dim, q)
    b = BlockCSR.from_padded(data, part)
    assert b.num_blocks == q
    assert b.num_instances == data.num_instances
    assert sum(b.block_dims) == data.dim
    # every stored nonzero is local to its block
    for l in range(q):
        idx, val = b.block(l)
        assert int(jnp.max(idx)) < b.block_dims[l] or b.block_dims[l] == 0
        assert int(jnp.min(idx)) >= 0
    # no nonzero lost: total mass matches
    assert b.nnz_total() == int(jnp.sum(data.values != 0.0))
    # per-worker rows shrink with q (the point of the layout)
    assert max(b.nnz_budgets) <= data.nnz_max
    if q >= 4:
        assert max(b.nnz_budgets) < data.nnz_max


def test_from_padded_single_block_shares_arrays():
    data = _data()
    b = BlockCSR.from_padded(data, balanced(data.dim, 1))
    assert b.indices[0] is data.indices
    assert b.values[0] is data.values


def test_from_padded_rejects_wrong_dim():
    data = _data(dim=100)
    with pytest.raises(ValueError, match="dim"):
        BlockCSR.from_padded(data, balanced(99, 4))


def test_lane_multiple_rounds_budgets():
    data = _data()
    b = BlockCSR.from_padded(data, balanced(data.dim, 4), lane_multiple=8)
    assert all(budget % 8 == 0 for budget in b.nnz_budgets)


def test_stacked_uniform_budget_and_equivalence():
    data = _data()
    q = 4
    part = balanced(data.dim, q)
    b = BlockCSR.from_padded(data, part)
    sidx, sval = b.stacked()
    n = data.num_instances
    assert sidx.shape == sval.shape == (q * n, max(b.nnz_budgets))
    w = jnp.asarray(RNG.normal(size=data.dim).astype(np.float32))
    total = jnp.zeros((n,), jnp.float32)
    for l in range(q):
        lo, hi = part.block(l)
        rows = slice(l * n, (l + 1) * n)
        total = total + local_margins(sidx[rows], sval[rows], w[lo:hi])
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(margins(data, w)), rtol=2e-4, atol=1e-5
    )
    with pytest.raises(ValueError, match="budget"):
        b.stacked(budget=1)


# ---------------------------------------------------------------------------
# equivalence with the masked global-CSR path (parametrized; always runs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [1, 2, 4, 7])
@pytest.mark.parametrize("strategy", ["balanced", "by_nnz"])
def test_margins_match_masked_path(q, strategy):
    data = _data(seed=q)
    if strategy == "balanced":
        part = balanced(data.dim, q)
    else:
        counts = feature_counts(
            np.asarray(data.indices), np.asarray(data.values), data.dim
        )
        part = by_nnz(data.dim, q, counts)
    b = BlockCSR.from_padded(data, part)
    w = jnp.asarray(RNG.normal(size=data.dim).astype(np.float32))
    for l in range(q):
        lo, hi = part.block(l)
        got = jax.jit(local_margins)(*b.block(l), w[lo:hi])
        want = jax.jit(masked_margins, static_argnames=("lo",))(
            data.indices, data.values, w[lo:hi], lo
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
        )


@pytest.mark.parametrize("q", [1, 2, 4, 7])
def test_scatter_matches_masked_path_and_global(q):
    data = _data(seed=10 + q)
    part = balanced(data.dim, q)
    b = BlockCSR.from_padded(data, part)
    coeffs = jnp.asarray(
        RNG.normal(size=data.num_instances).astype(np.float32)
    )
    pieces = []
    for l in range(q):
        lo, hi = part.block(l)
        got = local_scatter(*b.block(l), coeffs, b.block_dims[l])
        want = masked_scatter(data.indices, data.values, coeffs, lo, hi - lo)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )
        pieces.append(got)
    full = scatter_grad(data.indices, data.values, coeffs, data.dim)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(pieces)), np.asarray(full),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# hypothesis property: random partitions, sampled rows (CI; dev-only dep)
# ---------------------------------------------------------------------------


if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @given(
        st.integers(min_value=1, max_value=9),
        st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_margins_and_scatter_match_masked(q, seed):
        rng = np.random.default_rng(seed)
        data = _data(dim=211, n=13, nnz=7, seed=seed % 17)
        part = (
            balanced(data.dim, q)
            if seed % 2
            else _random_partition(rng, data.dim, max(q, 2))
        )
        b = BlockCSR.from_padded(data, part)
        w = jnp.asarray(rng.normal(size=data.dim).astype(np.float32))
        ids = jnp.asarray(
            rng.integers(0, data.num_instances, size=5).astype(np.int32)
        )
        coeffs = jnp.asarray(rng.normal(size=5).astype(np.float32))
        for l in range(part.num_blocks):
            lo, hi = part.block(l)
            idx_l, val_l = b.block(l)
            # margins over sampled rows (the inner-loop access pattern)
            got = local_margins(idx_l[ids], val_l[ids], w[lo:hi])
            want = masked_margins(
                data.indices[ids], data.values[ids], w[lo:hi], lo
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )
            got_s = local_scatter(idx_l[ids], val_l[ids], coeffs, hi - lo)
            want_s = masked_scatter(
                data.indices[ids], data.values[ids], coeffs, lo, hi - lo
            )
            np.testing.assert_allclose(
                np.asarray(got_s), np.asarray(want_s), rtol=1e-5, atol=1e-6
            )


# ---------------------------------------------------------------------------
# explicit-zero entries (the from_padded `val != 0.0` filter invariant)
# ---------------------------------------------------------------------------


def _data_with_explicit_zeros(dim=120, n=9, nnz=6, seed=5, block_lo=None):
    """Padded rows where some stored entries have value exactly 0.0 —
    including, when ``block_lo`` is given, an explicit zero AT a block's
    lower bound, whose re-indexed form (local id 0, value 0.0) collides
    exactly with the padding pattern."""
    rng = np.random.default_rng(seed)
    base = make_sparse_classification(
        dim=dim, num_instances=n, nnz_per_instance=nnz, seed=seed
    )
    val = np.asarray(base.values).copy()
    idx = np.asarray(base.indices).copy()
    # zero out one genuine entry per even row (index kept: explicit zero)
    for i in range(0, n, 2):
        val[i, rng.integers(0, nnz)] = 0.0
    if block_lo is not None:
        # a stored (id == block lower bound, value 0.0) entry
        idx[1, 0] = block_lo
        val[1, 0] = 0.0
    return PaddedCSR(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=base.labels, dim=dim,
    )


@pytest.mark.parametrize("q", [2, 3, 4])
def test_explicit_zeros_margins_and_scatter_match_masked(q):
    """Explicit zeros are dropped by from_padded — and that is safe:
    margins and scatter match the masked oracle (which keeps them) bit
    for contribution, because a zero value contributes nothing."""
    part = balanced(120, q)
    lo1 = part.block(1)[0]  # put a colliding (id lo, 0.0) in block 1
    data = _data_with_explicit_zeros(block_lo=lo1)
    b = BlockCSR.from_padded(data, part)
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=data.dim).astype(np.float32))
    coeffs = jnp.asarray(
        rng.normal(size=data.num_instances).astype(np.float32)
    )
    for l in range(q):
        lo, hi = part.block(l)
        got = local_margins(*b.block(l), w[lo:hi])
        want = masked_margins(data.indices, data.values, w[lo:hi], lo)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )
        got_s = local_scatter(*b.block(l), coeffs, hi - lo)
        want_s = masked_scatter(data.indices, data.values, coeffs, lo, hi - lo)
        np.testing.assert_allclose(
            np.asarray(got_s), np.asarray(want_s), rtol=1e-5, atol=1e-6
        )


def test_explicit_zeros_dropped_from_budgets_and_counts():
    """from_padded counts only value != 0 entries: nnz_total excludes the
    explicit zeros, and per-block budgets never grow because of them."""
    data = _data_with_explicit_zeros()
    b = BlockCSR.from_padded(data, balanced(data.dim, 3))
    assert b.nnz_total() == int(jnp.sum(data.values != 0.0))
    dense_rows = (np.asarray(data.values) != 0.0).sum(axis=1)
    assert max(b.nnz_budgets) <= int(dense_rows.max())


if HAS_HYPOTHESIS:

    @given(
        st.integers(min_value=2, max_value=6),
        st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_explicit_zeros_preserve_margins(q, seed):
        rng = np.random.default_rng(seed)
        data = _data_with_explicit_zeros(dim=97, n=7, nnz=5, seed=seed % 13)
        part = balanced(data.dim, q)
        b = BlockCSR.from_padded(data, part)
        w = jnp.asarray(rng.normal(size=data.dim).astype(np.float32))
        ids = jnp.asarray(
            rng.integers(0, data.num_instances, size=4).astype(np.int32)
        )
        coeffs = jnp.asarray(rng.normal(size=4).astype(np.float32))
        for l in range(part.num_blocks):
            lo, hi = part.block(l)
            idx_l, val_l = b.block(l)
            got = local_margins(idx_l[ids], val_l[ids], w[lo:hi])
            want = masked_margins(
                data.indices[ids], data.values[ids], w[lo:hi], lo
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )
            got_s = local_scatter(idx_l[ids], val_l[ids], coeffs, hi - lo)
            want_s = masked_scatter(
                data.indices[ids], data.values[ids], coeffs, lo, hi - lo
            )
            np.testing.assert_allclose(
                np.asarray(got_s), np.asarray(want_s), rtol=1e-5, atol=1e-6
            )


# ---------------------------------------------------------------------------
# vectorized to_dense (satellite regression)
# ---------------------------------------------------------------------------


def test_to_dense_shape_dtype_and_values():
    data = _data(dim=300, n=20, nnz=7, seed=4)
    dense = data.to_dense()
    assert dense.shape == (data.dim, data.num_instances)
    assert dense.dtype == np.float32
    # oracle: the original per-instance np.add.at loop
    idx = np.asarray(data.indices)
    val = np.asarray(data.values)
    want = np.zeros_like(dense)
    for i in range(data.num_instances):
        np.add.at(want[:, i], idx[i], val[i])
    np.testing.assert_array_equal(dense, want)


def test_to_dense_accumulates_repeated_indices():
    data = PaddedCSR(
        indices=jnp.asarray([[1, 1, 0], [2, 0, 0]], jnp.int32),
        values=jnp.asarray([[1.0, 2.0, 0.0], [4.0, 0.0, 0.0]], jnp.float32),
        labels=jnp.asarray([1.0, -1.0]),
        dim=4,
    )
    dense = data.to_dense()
    assert dense[1, 0] == pytest.approx(3.0)  # repeated index summed
    assert dense[2, 1] == pytest.approx(4.0)
    assert dense[0, 0] == pytest.approx(0.0)  # zero-value padding ignored
