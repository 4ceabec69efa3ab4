"""The two fused FD-SVRG hot-path kernels (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py, swept over shapes and tilings.

Bit-identity is part of the contract: inside a jit, the interpret-mode
kernels must reproduce the reference expression tree exactly — that is
what makes ``use_kernels=True`` produce bit-identical iterates (asserted
end-to-end in test_fdsvrg_core.py / test_fdsvrg_shardmap.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

try:
    import hypothesis  # noqa: F401  (dev-only dep; see requirements-dev.txt)

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


RNG = np.random.default_rng(0)


def _case(d, n, nnz, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, d, size=(n, nnz)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(n, nnz)).astype(np.float32))
    return w, idx, val


# ---------------------------------------------------------------------------
# sparse_margin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "d,n,nnz", [(64, 8, 4), (300, 37, 9), (1024, 128, 16), (50, 1, 1)]
)
def test_sparse_margin_matches_ref_bitwise(d, n, nnz):
    w, idx, val = _case(d, n, nnz, seed=d)
    got = ops.sparse_margins(idx, val, w, interpret=True)
    want = jax.jit(ref.sparse_margin_ref)(w, idx, val)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.float32


@pytest.mark.parametrize("block_rows", [1, 4, 8, 16])
def test_sparse_margin_row_tiling_sweep(block_rows):
    w, idx, val = _case(200, 23, 7, seed=1)  # 23 rows: exercises padding
    got = ops.sparse_margins(idx, val, w, block_rows=block_rows, interpret=True)
    want = jax.jit(ref.sparse_margin_ref)(w, idx, val)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
    )


def test_sparse_margin_zero_value_padding_is_inert():
    """(idx 0, val 0) padding — BlockCSR's convention — contributes 0."""
    w = jnp.asarray(RNG.normal(size=10).astype(np.float32))
    idx = jnp.asarray([[3, 0, 0], [7, 2, 0]], jnp.int32)
    val = jnp.asarray([[2.0, 0.0, 0.0], [1.0, 1.0, 0.0]], jnp.float32)
    got = ops.sparse_margins(idx, val, w, interpret=True)
    want = jnp.asarray([2.0 * w[3], w[7] + w[2]])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# The TPU body (scalar-loop gather over lane-major w) under the interpreter:
# its gather is exact, and its margins agree with the reference to float32
# rounding — XLA:CPU may contract the reference's fused gather-reduce into
# multiply-adds that the loop body's reduction of a written-out tile skips.


@pytest.mark.parametrize("d,n,nnz,block_rows", [
    (300, 37, 9, 8), (1000, 20, 5, None), (129, 3, 130, None),
])
def test_sparse_margin_scalar_loop_gather_is_exact(d, n, nnz, block_rows):
    w, idx, _ = _case(d, n, nnz, seed=d + n)
    for k in (0, nnz - 1):
        one_hot = jnp.zeros((n, nnz), jnp.float32).at[:, k].set(1.0)
        got = ops.sparse_margins(
            idx, one_hot, w, block_rows=block_rows, interpret=True,
            scalar_loops=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w[idx[:, k]]))


@pytest.mark.parametrize("d,n,nnz", [(64, 8, 4), (300, 37, 9), (1024, 40, 16)])
def test_sparse_margin_scalar_loops_match_ref(d, n, nnz):
    w, idx, val = _case(d, n, nnz, seed=d)
    got = ops.sparse_margins(idx, val, w, interpret=True, scalar_loops=True)
    want = jax.jit(ref.sparse_margin_ref)(w, idx, val)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


# ---------------------------------------------------------------------------
# fused_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d,u,nnz", [(64, 1, 4), (300, 5, 9), (1024, 16, 8)])
@pytest.mark.parametrize("eta,lam", [(0.1, 1e-4), (0.5, 0.0), (0.01, 1e-2)])
def test_fused_update_matches_ref_bitwise(d, u, nnz, eta, lam):
    w, idx, val = _case(d, u, nnz, seed=d + u)
    coef = jnp.asarray(RNG.normal(size=u).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=d).astype(np.float32))
    eta_arr = jnp.float32(eta)
    got = ops.fused_block_update(
        w, idx, val, coef, z, eta_arr, lam=lam, interpret=True
    )
    want = jax.jit(ref.fused_update_ref, static_argnames=("lam",))(
        w, idx, val, coef, z, eta_arr, lam=lam
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_update_masked_step_is_identity():
    """eta * mask = 0 (Option II tail) must return w unchanged."""
    w, idx, val = _case(100, 3, 5, seed=9)
    coef = jnp.asarray(RNG.normal(size=3).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=100).astype(np.float32))
    got = ops.fused_block_update(
        w, idx, val, coef, z, jnp.float32(0.0), lam=1e-3, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


def test_fused_update_collapses_three_passes():
    """The fusion target: scatter pass + add pass + axpy pass == kernel."""
    w, idx, val = _case(256, 4, 6, seed=2)
    coef = jnp.asarray(RNG.normal(size=4).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=256).astype(np.float32))
    eta, lam = 0.2, 1e-3

    @jax.jit
    def three_pass(w, idx, val, coef, z):
        from repro.data.block_csr import local_scatter

        g = local_scatter(idx, val, coef, w.shape[0])  # pass 1: densify
        g = g + z + lam * w  # pass 2: combine
        return w - eta * g  # pass 3: axpy

    got = ops.fused_block_update(
        w, idx, val, coef, z, jnp.float32(eta), lam=lam, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(three_pass(w, idx, val, coef, z)),
        rtol=1e-6, atol=1e-7,
    )


# ---------------------------------------------------------------------------
# prox_update (the whole-regularizer-family fused kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d,u,nnz", [(64, 1, 4), (300, 5, 9), (1024, 16, 8)])
@pytest.mark.parametrize(
    "lam,lam1,lam2",
    [
        (1e-4, 0.0, 0.0),  # l2: the prox stages elide at trace time
        (0.0, 1e-2, 0.0),  # l1: soft-threshold
        (0.0, 1e-2, 1e-3),  # elastic net: threshold + shrink
        (0.0, 0.0, 0.0),  # none
    ],
)
def test_prox_update_matches_ref_bitwise(d, u, nnz, lam, lam1, lam2):
    w, idx, val = _case(d, u, nnz, seed=d + u)
    coef = jnp.asarray(RNG.normal(size=u).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=d).astype(np.float32))
    eta = jnp.float32(0.2)
    got = ops.fused_block_prox_update(
        w, idx, val, coef, z, eta, lam=lam, lam1=lam1, lam2=lam2, interpret=True
    )
    want = jax.jit(
        ref.prox_update_ref, static_argnames=("lam", "lam1", "lam2")
    )(w, idx, val, coef, z, eta, lam=lam, lam1=lam1, lam2=lam2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("chunk_rows", [8, 16, 48])
@pytest.mark.parametrize(
    "lam,lam1,lam2", [(1e-4, 0.0, 0.0), (0.0, 1e-2, 1e-3)]
)
def test_prox_update_scalar_loops_match_ref(chunk_rows, lam, lam1, lam2):
    """The TPU body — the scatter as a scalar loop over row chunks of the
    lane-major block — under the interpreter, against the reference to
    float32 rounding (it adds the scattered terms onto z + lam * w, the
    association XLA picks for the reference only by context).  d = 6000
    spans 47 lane rows, so every chunking leaves ids in several chunks."""
    from repro.kernels.prox_update import prox_update

    d, u, nnz = 6000, 4, 12
    w, idx, val = _case(d, u, nnz, seed=7)
    idx = idx.at[0, :3].set(idx[1, 0])  # duplicate ids accumulate in order
    coef = jnp.asarray(RNG.normal(size=u).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=d).astype(np.float32))
    eta = jnp.float32(0.2)
    rows = -(-47 // chunk_rows) * chunk_rows
    lane_major = lambda v: jnp.pad(v, (0, rows * 128 - d)).reshape(rows, 128)
    got = prox_update(
        lane_major(w), idx, val, coef, lane_major(z), eta.reshape(1),
        chunk_rows=chunk_rows, lam=lam, lam1=lam1, lam2=lam2, interpret=True,
    ).reshape(-1)[:d]
    want = jax.jit(
        ref.prox_update_ref, static_argnames=("lam", "lam1", "lam2")
    )(w, idx, val, coef, z, eta, lam=lam, lam1=lam1, lam2=lam2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
    )


def test_prox_update_wrapper_scalar_loops_match_ref():
    w, idx, val = _case(300, 5, 9, seed=3)
    coef = jnp.asarray(RNG.normal(size=5).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=300).astype(np.float32))
    eta = jnp.float32(0.1)
    got = ops.fused_block_prox_update(
        w, idx, val, coef, z, eta, lam=1e-3, interpret=True, scalar_loops=True
    )
    want = ref.prox_update_ref(
        w, idx, val, coef, z, eta, lam=1e-3, lam1=0.0, lam2=0.0
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
    )


def test_prox_update_l2_path_reproduces_fused_update():
    """lam1 = lam2 = 0 must leave exactly the fused_update expression tree —
    the L2 family keeps its historical bit-identity."""
    w, idx, val = _case(256, 4, 6, seed=2)
    coef = jnp.asarray(RNG.normal(size=4).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=256).astype(np.float32))
    eta = jnp.float32(0.1)
    a = ops.fused_block_update(w, idx, val, coef, z, eta, lam=1e-3, interpret=True)
    b = ops.fused_block_prox_update(
        w, idx, val, coef, z, eta, lam=1e-3, lam1=0.0, lam2=0.0, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prox_update_masked_step_is_identity():
    """eta * mask = 0 (Option II tail): threshold 0, shrink 1 — w unchanged
    (up to the sign of zero, which compares equal)."""
    w, idx, val = _case(100, 3, 5, seed=9)
    coef = jnp.asarray(RNG.normal(size=3).astype(np.float32))
    z = jnp.asarray(RNG.normal(size=100).astype(np.float32))
    got = ops.fused_block_prox_update(
        w, idx, val, coef, z, jnp.float32(0.0), lam=0.0, lam1=1e-2, lam2=1e-3,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


def test_prox_update_thresholds_small_coordinates():
    """Coordinates whose post-step magnitude falls below eta*lam1 come out
    exactly zero — the sparsity mechanism itself."""
    d = 32
    w = jnp.full((d,), 1e-4, jnp.float32)
    idx = jnp.zeros((1, 1), jnp.int32)
    val = jnp.zeros((1, 1), jnp.float32)
    coef = jnp.zeros((1,), jnp.float32)
    z = jnp.zeros((d,), jnp.float32)
    out = ops.fused_block_prox_update(
        w, idx, val, coef, z, jnp.float32(0.1), lam=0.0, lam1=1.0, lam2=0.0,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), np.zeros(d, np.float32))


# ---------------------------------------------------------------------------
# hypothesis properties (CI; dev-only dep)
# ---------------------------------------------------------------------------


if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @given(
        st.integers(min_value=1, max_value=200),
        st.integers(min_value=1, max_value=12),
        st.integers(min_value=1, max_value=10),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_sparse_margin_interpret_equivalence(d, n, nnz):
        w, idx, val = _case(d, n, nnz, seed=d * 31 + n)
        got = ops.sparse_margins(idx, val, w, interpret=True)
        want = jax.jit(ref.sparse_margin_ref)(w, idx, val)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(
        st.integers(min_value=1, max_value=200),
        st.integers(min_value=1, max_value=8),
        st.floats(min_value=1e-4, max_value=1.0),
        st.floats(min_value=0.0, max_value=0.1),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_fused_update_interpret_equivalence(d, u, eta, lam):
        rng = np.random.default_rng(d * 7 + u)
        w, idx, val = _case(d, u, 5, seed=d + u)
        coef = jnp.asarray(rng.normal(size=u).astype(np.float32))
        z = jnp.asarray(rng.normal(size=d).astype(np.float32))
        got = ops.fused_block_update(
            w, idx, val, coef, z, jnp.float32(eta), lam=float(lam),
            interpret=True,
        )
        want = jax.jit(ref.fused_update_ref, static_argnames=("lam",))(
            w, idx, val, coef, z, jnp.float32(eta), lam=float(lam)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(
        st.integers(min_value=1, max_value=200),
        st.integers(min_value=1, max_value=8),
        st.floats(min_value=1e-4, max_value=1.0),
        st.floats(min_value=0.0, max_value=0.1),
        st.floats(min_value=0.0, max_value=0.1),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_prox_update_interpret_equivalence(d, u, eta, lam1, lam2):
        rng = np.random.default_rng(d * 13 + u)
        w, idx, val = _case(d, u, 5, seed=d + 2 * u)
        coef = jnp.asarray(rng.normal(size=u).astype(np.float32))
        z = jnp.asarray(rng.normal(size=d).astype(np.float32))
        got = ops.fused_block_prox_update(
            w, idx, val, coef, z, jnp.float32(eta), lam=0.0,
            lam1=float(lam1), lam2=float(lam2), interpret=True,
        )
        want = jax.jit(
            ref.prox_update_ref, static_argnames=("lam", "lam1", "lam2")
        )(w, idx, val, coef, z, jnp.float32(eta), lam=0.0,
          lam1=float(lam1), lam2=float(lam2))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
