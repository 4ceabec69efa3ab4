"""Lazy O(nnz) delayed-decay inner steps vs the dense oracle.

The equivalence contract, in three layers:

1. **Per-step oracle, bitwise.**  The exact-lazy epoch must be
   bit-identical to the *per-step dense oracle* — :func:`_sim_update`
   (the dense fused update / prox update) iterated step by step — across
   every regularizer family, worker count, kernel mode, and step-mask
   option.  The per-step oracle is the q-independent reference; the
   fused ``_inner_epoch`` scan itself is NOT q-stable for the prox
   family (see layer 3).
2. **Kernel vs reference, bitwise.**  Each of the four lazy Pallas
   kernels (interpret mode on CPU) reproduces its jnp reference oracle
   exactly.
3. **Drivers.**  ``lazy_updates="exact"`` is bit-identical to the eager
   run for the serial driver, the object-level simulation (any q), and
   ``run_fdsvrg`` at q=1 — and ulp-bounded at q>1 for l1/elastic-net,
   where the *dense* scan's own bits move: XLA contracts the soft
   threshold ``|v| - eta*lam`` into an FMA at some q and pre-rounds
   ``fl(eta*lam)`` at others (verified coordinate-by-coordinate against
   both emulations), so no single lazy implementation can bit-match the
   fused scan at every q.  The probabilistic variant is checked for
   unbiasedness (per-feature expected update == dense, over many draws)
   and end-to-end convergence.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fdsvrg, losses
from repro.core.fdsvrg import (
    SVRGConfig,
    _check_lazy,
    fdsvrg_worker_simulation,
    run_fdsvrg,
    run_serial_svrg,
)
from repro.core.partition import balanced
from repro.data.block_csr import BlockCSR
from repro.data.sparse import PaddedCSR
from repro.data.synthetic import make_sparse_classification
from repro.kernels import ops, ref

REGS = {
    "none": losses.no_reg(),
    "l2": losses.l2(1e-3),
    "l1": losses.l1(1e-3),
    "elastic_net": losses.elastic_net(1e-3, 1e-3),
}

#: (lam, lam1, lam2) triples the four lazy kernels are exercised with.
LAM_TRIPLES = {
    "none": (0.0, 0.0, 0.0),
    "l2": (1e-3, 0.0, 0.0),
    "l1": (0.0, 1e-3, 0.0),
    "elastic_net": (0.0, 1e-3, 1e-3),
}


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


def _ulp_diff(a, b) -> int:
    """Max distance in float32 ulps, via the lexicographic int mapping."""
    ia = _bits(a).astype(np.int64)
    ib = _bits(b).astype(np.int64)
    ia = np.where(ia >= 0x80000000, 0x80000000 - ia, ia)
    ib = np.where(ib >= 0x80000000, 0x80000000 - ib, ib)
    return int(np.abs(ia - ib).max()) if ia.size else 0


def oracle_epoch(bd, labels, w, z, s0, samples, eta, mask, reg, use_kernels):
    """The per-step dense oracle: one _sim_update per block per inner step,
    margins summed in the shared tree order — the q-independent reference
    the exact-lazy epoch must reproduce bit-for-bit."""
    q = bd.num_blocks
    bounds = [0]
    for d_ in bd.block_dims:
        bounds.append(bounds[-1] + d_)
    blocks = [w[bounds[l]:bounds[l + 1]] for l in range(q)]
    z_blocks = [z[bounds[l]:bounds[l + 1]] for l in range(q)]
    loss = losses.logistic
    u = samples.shape[1]
    for m in range(samples.shape[0]):
        ids = samples[m]
        rows = [(bd.indices[l][ids], bd.values[l][ids]) for l in range(q)]
        parts = [
            fdsvrg._sim_margins(rows[l][0], rows[l][1], blocks[l], use_kernels)
            for l in range(q)
        ]
        s_m = fdsvrg.tree_order_sum(parts)
        y = labels[ids]
        coef = (loss.dvalue(s_m, y) - loss.dvalue(s0[ids], y)) / u
        eta_m = jnp.asarray(eta * float(mask[m]), dtype=jnp.float32)
        for l in range(q):
            blocks[l] = fdsvrg._sim_update(
                blocks[l], rows[l][0], rows[l][1], coef, z_blocks[l], eta_m,
                reg.name, reg.lam, use_kernels, lam2=reg.lam2,
            )
    return jnp.concatenate(blocks) if q > 1 else blocks[0]


def _lazy_epoch(bd, labels, w, z, s0, samples, eta, mask, reg, use_kernels):
    klams = fdsvrg._kernel_lams(reg, use_kernels)
    return fdsvrg._lazy_inner_epoch(
        bd.indices, bd.values, labels, w, z, s0, jnp.asarray(samples), eta,
        jnp.asarray(mask), None, "logistic", reg.name, reg.lam,
        bd.block_dims, use_kernels, "exact", lam2=reg.lam2,
        kernel_lams=klams,
    )


def _epoch_case(seed=7, d=256, n=48, nnz=6, m_steps=12, u=2):
    data = make_sparse_classification(
        dim=d, num_instances=n, nnz_per_instance=nnz, seed=seed
    )
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=data.dim).astype(np.float32) * 0.01)
    samples = rng.integers(0, n, size=(m_steps, u)).astype(np.int32)
    return data, w0, samples


# ---------------------------------------------------------------------------
# 1. exact-lazy epoch == per-step dense oracle, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option", ["I", "II"])
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("reg_name", sorted(REGS))
@pytest.mark.parametrize("q", [1, 3])
def test_exact_epoch_matches_per_step_oracle(q, reg_name, use_kernels, option):
    data, w0, samples = _epoch_case()
    bd = BlockCSR.from_padded(data, balanced(data.dim, q))
    reg = REGS[reg_name]
    m_steps = samples.shape[0]
    mask = (
        np.ones(m_steps, np.float32)
        if option == "I"
        else (np.arange(m_steps) < m_steps - 4).astype(np.float32)
    )
    z, s0 = fdsvrg._full_grad_blocks(
        bd.groups, data.labels, w0, "logistic", bd.block_dims,
        use_kernels,
    )
    want = oracle_epoch(
        bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, use_kernels
    )
    got = _lazy_epoch(
        bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, use_kernels
    )
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_never_touched_features_match_oracle():
    """Features no sampled row ever touches must still follow the dense
    decay trajectory exactly — they only ever see the epoch-end flush."""
    rng = np.random.default_rng(3)
    d, n, nnz, m_steps = 64, 16, 3, 10
    # every row's ids live in [0, 8): features 8.. are never touched
    idx = rng.integers(0, 8, size=(n, nnz)).astype(np.int32)
    val = rng.normal(size=(n, nnz)).astype(np.float32)
    labels = np.sign(rng.normal(size=n)).astype(np.float32)
    data = PaddedCSR(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(labels), dim=d,
    )
    bd = BlockCSR.from_padded(data, balanced(d, 1))
    w0 = jnp.asarray(rng.normal(size=d).astype(np.float32))
    samples = rng.integers(0, n, size=(m_steps, 2)).astype(np.int32)
    mask = np.ones(m_steps, np.float32)
    for reg in REGS.values():
        z, s0 = fdsvrg._full_grad_blocks(
            bd.groups, data.labels, w0, "logistic",
            bd.block_dims, False,
        )
        want = oracle_epoch(
            bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, False
        )
        got = _lazy_epoch(
            bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, False
        )
        np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=reg.name)
        # and for the decaying regularizers the untouched tail really is
        # nontrivial: it moved (for "none" it rightly stays put — z = 0
        # there and there is no smooth/prox term to apply)
        if reg.name != "none":
            assert not np.array_equal(np.asarray(got)[8:], np.asarray(w0)[8:])


def test_first_and_last_step_only_touches():
    """A feature touched ONLY at step 0 must replay all later decay at the
    flush; one touched ONLY at step M-1 must catch up the whole prefix
    first.  Both bit-equal to the oracle."""
    rng = np.random.default_rng(5)
    d, m_steps = 32, 8
    # row r touches feature r+1 (plus a shared feature 0)
    n = m_steps
    idx = np.stack([np.zeros(n), np.arange(1, n + 1)], axis=1).astype(np.int32)
    val = rng.normal(size=(n, 2)).astype(np.float32)
    labels = np.sign(rng.normal(size=n)).astype(np.float32)
    data = PaddedCSR(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(labels), dim=d,
    )
    bd = BlockCSR.from_padded(data, balanced(d, 1))
    w0 = jnp.asarray(rng.normal(size=d).astype(np.float32))
    samples = np.arange(m_steps, dtype=np.int32)[:, None]  # step m draws row m
    mask = np.ones(m_steps, np.float32)
    for reg in REGS.values():
        z, s0 = fdsvrg._full_grad_blocks(
            bd.groups, data.labels, w0, "logistic",
            bd.block_dims, False,
        )
        want = oracle_epoch(
            bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, False
        )
        got = _lazy_epoch(
            bd, data.labels, w0, z, s0, samples, 0.1, mask, reg, False
        )
        np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=reg.name)


def test_padding_collision_id_zero_value_zero():
    """CSR padding lanes carry (id == block lo, value 0.0).  A row that
    ALSO genuinely touches local id 0 forces the dedup to merge real and
    padding contributions at the same id — the classic collision — and
    the catch-up must not replay id 0 twice."""
    rng = np.random.default_rng(11)
    d, n, m_steps = 16, 6, 6
    idx = np.zeros((n, 4), dtype=np.int32)
    val = np.zeros((n, 4), dtype=np.float32)
    for r in range(n):
        idx[r, 0] = 0  # every row genuinely touches id 0...
        val[r, 0] = float(rng.normal())
        idx[r, 1] = int(rng.integers(1, d))
        val[r, 1] = float(rng.normal())
        # ...lanes 2-3 stay (0, 0.0) padding, colliding with lane 0
    labels = np.sign(rng.normal(size=n)).astype(np.float32)
    data = PaddedCSR(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(labels), dim=d,
    )
    bd = BlockCSR.from_padded(data, balanced(d, 1))
    w0 = jnp.asarray(rng.normal(size=d).astype(np.float32))
    samples = rng.integers(0, n, size=(m_steps, 2)).astype(np.int32)
    mask = np.ones(m_steps, np.float32)
    for reg in REGS.values():
        for use_kernels in (False, True):
            z, s0 = fdsvrg._full_grad_blocks(
                bd.groups, data.labels, w0, "logistic",
                bd.block_dims, use_kernels,
            )
            want = oracle_epoch(
                bd, data.labels, w0, z, s0, samples, 0.1, mask, reg,
                use_kernels,
            )
            got = _lazy_epoch(
                bd, data.labels, w0, z, s0, samples, 0.1, mask, reg,
                use_kernels,
            )
            np.testing.assert_array_equal(
                _bits(got), _bits(want),
                err_msg=f"{reg.name} kernels={use_kernels}",
            )


# ---------------------------------------------------------------------------
# 2. the four lazy kernels vs their jnp reference oracles, bitwise
# ---------------------------------------------------------------------------


def _kernel_case(seed, d=64, u=3, nnz=4, m_steps=9):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    last = jnp.asarray(rng.integers(0, m_steps, size=d).astype(np.int32))
    z = jnp.asarray(rng.normal(size=d).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, d, size=(u, nnz)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(u, nnz)).astype(np.float32))
    coef = jnp.asarray(rng.normal(size=u).astype(np.float32))
    corr = jnp.asarray(rng.uniform(1.0, 20.0, size=d).astype(np.float32))
    return w, last, z, idx, val, coef, corr


@pytest.mark.parametrize("lams", sorted(LAM_TRIPLES))
def test_lazy_catchup_kernel_matches_ref_bitwise(lams):
    lam, lam1, lam2 = LAM_TRIPLES[lams]
    w, last, z, idx, _, _, _ = _kernel_case(1)
    eta = jnp.float32(0.1)
    m = jnp.asarray(6, jnp.int32)
    stop = jnp.asarray(7, jnp.int32)
    # jit the ref (the fused-kernel test idiom): eager op-by-op rounding
    # differs from the compiled kernel by FMA contraction
    want_w, want_last = jax.jit(
        ref.lazy_catchup_ref, static_argnames=("lam1", "lam2")
    )(w, last, z, idx, eta, m, stop, lam=jnp.float32(lam), lam1=lam1,
      lam2=lam2)
    got_w, got_last = ops.lazy_block_catchup(
        w, last, z, idx, eta, m, stop, lam=jnp.float32(lam), lam1=lam1,
        lam2=lam2, interpret=True,
    )
    np.testing.assert_array_equal(_bits(got_w), _bits(want_w))
    np.testing.assert_array_equal(np.asarray(got_last), np.asarray(want_last))


@pytest.mark.parametrize("lams", sorted(LAM_TRIPLES))
@pytest.mark.parametrize("eta_m", [0.1, 0.0])
def test_lazy_touch_kernel_matches_ref_bitwise(lams, eta_m):
    lam, lam1, lam2 = LAM_TRIPLES[lams]
    w, _, z, idx, val, coef, _ = _kernel_case(2)
    want = jax.jit(
        ref.lazy_touch_update_ref, static_argnames=("lam", "lam1", "lam2")
    )(w, idx, val, coef, z, jnp.float32(eta_m), lam=lam, lam1=lam1,
      lam2=lam2)
    got = ops.lazy_block_touch_update(
        w, idx, val, coef, z, jnp.float32(eta_m), lam=lam, lam1=lam1,
        lam2=lam2, interpret=True,
    )
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("lams", sorted(LAM_TRIPLES))
def test_lazy_flush_kernel_matches_ref_bitwise(lams):
    lam, lam1, lam2 = LAM_TRIPLES[lams]
    w, last, z, _, _, _, _ = _kernel_case(3)
    eta = jnp.float32(0.1)
    total = jnp.asarray(9, jnp.int32)
    stop = jnp.asarray(5, jnp.int32)  # Option II: masked tail to replay
    want = jax.jit(
        ref.lazy_flush_ref, static_argnames=("lam1", "lam2")
    )(w, last, z, eta, total, stop, lam=jnp.float32(lam), lam1=lam1,
      lam2=lam2)
    got = ops.lazy_block_flush(
        w, last, z, eta, total, stop, lam=jnp.float32(lam), lam1=lam1,
        lam2=lam2, interpret=True,
    )
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("lams", sorted(LAM_TRIPLES))
def test_lazy_proba_kernel_matches_ref_bitwise(lams):
    lam, lam1, lam2 = LAM_TRIPLES[lams]
    w, _, z, idx, val, coef, corr = _kernel_case(4)
    want = jax.jit(
        ref.lazy_proba_update_ref, static_argnames=("lam", "lam1", "lam2")
    )(w, idx, val, coef, z, corr, jnp.float32(0.1), lam=lam, lam1=lam1,
      lam2=lam2)
    got = ops.lazy_block_proba_update(
        w, idx, val, coef, z, corr, jnp.float32(0.1), lam=lam, lam1=lam1,
        lam2=lam2, interpret=True,
    )
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_step_corrections_values():
    """corr_j = 1 / (1 - (1 - nnz_col_j/n)^u); untouchable features (zero
    column count) are pinned to 1 so they contribute no NaN/inf."""
    nnz_col = jnp.asarray([0, 1, 4, 8], jnp.int32)
    n, u = 8, 2
    corr = np.asarray(ops.step_corrections(nnz_col, n, u))
    assert corr[0] == 1.0
    for j, c in ((1, 1), (2, 4), (3, 8)):
        p = 1.0 - (1.0 - c / n) ** u
        np.testing.assert_allclose(corr[j], 1.0 / p, rtol=1e-6)
    assert np.isfinite(corr).all()


# ---------------------------------------------------------------------------
# 3. drivers
# ---------------------------------------------------------------------------


def _driver_data(seed=7):
    return make_sparse_classification(
        dim=256, num_instances=48, nnz_per_instance=6, seed=seed
    )


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("reg_name", sorted(REGS))
def test_serial_lazy_exact_bitwise(reg_name, use_kernels):
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=10, outer_iters=2, seed=5,
                     option="II")
    reg = REGS[reg_name]
    a = run_serial_svrg(data, losses.logistic, reg, cfg,
                        use_kernels=use_kernels)
    b = run_serial_svrg(data, losses.logistic, reg, cfg,
                        use_kernels=use_kernels, lazy_updates="exact")
    np.testing.assert_array_equal(_bits(a.w), _bits(b.w))
    for ha, hb in zip(a.history, b.history):
        assert ha.objective == hb.objective


@pytest.mark.parametrize("reg_name", sorted(REGS))
def test_fdsvrg_q1_lazy_exact_bitwise(reg_name):
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=10, outer_iters=2, seed=5)
    part = balanced(data.dim, 1)
    reg = REGS[reg_name]
    a = run_fdsvrg(data, part, losses.logistic, reg, cfg)
    b = run_fdsvrg(data, part, losses.logistic, reg, cfg,
                   lazy_updates="exact")
    np.testing.assert_array_equal(_bits(a.w), _bits(b.w))


@pytest.mark.parametrize("reg_name", ["none", "l2"])
def test_fdsvrg_multiblock_smooth_bitwise(reg_name):
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=10, outer_iters=2, seed=5)
    part = balanced(data.dim, 3)
    reg = REGS[reg_name]
    a = run_fdsvrg(data, part, losses.logistic, reg, cfg)
    b = run_fdsvrg(data, part, losses.logistic, reg, cfg,
                   lazy_updates="exact")
    np.testing.assert_array_equal(_bits(a.w), _bits(b.w))


@pytest.mark.parametrize("reg_name", ["l1", "elastic_net"])
def test_fdsvrg_multiblock_prox_ulp_bounded(reg_name):
    """At q>1 the prox family is ulp-bounded, not bitwise, against the
    fused dense scan — and the slack is in the DENSE side, not the lazy
    side.  Verified coordinate-by-coordinate with double-precision FMA
    emulation: the dense ``_inner_epoch`` soft threshold evaluates
    ``|v| - eta*lam`` as a single-rounding FMA at q=3 but against the
    pre-rounded ``fl(eta*lam)`` at q=1, so its own bits are q-dependent.
    The lazy epoch is pinned bitwise to the q-independent per-step oracle
    (the tests above); here we only require it to stay within a small ulp
    envelope of the fused scan — per inner step the two threshold
    evaluations differ by 1-2 ulp, and the divergence compounds across
    outer iterations because the full gradient is recomputed from the
    (slightly different) iterate."""
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=10, outer_iters=2, seed=5)
    part = balanced(data.dim, 3)
    reg = REGS[reg_name]
    a = run_fdsvrg(data, part, losses.logistic, reg, cfg)
    b = run_fdsvrg(data, part, losses.logistic, reg, cfg,
                   lazy_updates="exact")
    assert _ulp_diff(a.w, b.w) <= 32
    np.testing.assert_allclose(np.asarray(a.w), np.asarray(b.w), rtol=1e-5,
                               atol=1e-7)


@pytest.mark.parametrize("q", [1, 3])
@pytest.mark.parametrize("reg_name", sorted(REGS))
def test_sim_driver_lazy_exact_bitwise(reg_name, q):
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=10, outer_iters=2, seed=5,
                     option="II")
    part = balanced(data.dim, q)
    reg = REGS[reg_name]
    a = fdsvrg_worker_simulation(data, part, losses.logistic, reg, cfg)
    b = fdsvrg_worker_simulation(data, part, losses.logistic, reg, cfg,
                                 lazy_updates="exact")
    np.testing.assert_array_equal(_bits(a.w), _bits(b.w))


# ---------------------------------------------------------------------------
# probabilistic variant: unbiasedness + convergence
# ---------------------------------------------------------------------------


def test_proba_expected_update_matches_dense():
    """Over many independent single-step draws, the per-feature mean
    update of the probabilistic variant must match the dense oracle's:
    the decay is applied with probability p_j but scaled by 1/p_j."""
    rng = np.random.default_rng(0)
    d, n, nnz, u, draws = 64, 32, 4, 2, 512
    data = make_sparse_classification(
        dim=d, num_instances=n, nnz_per_instance=nnz, seed=9
    )
    bd = BlockCSR.from_padded(data, balanced(d, 1))
    reg = losses.l2(1e-2)
    w0 = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
    eta = 0.05
    z, s0 = fdsvrg._full_grad_blocks(
        bd.groups, data.labels, w0, "logistic", bd.block_dims,
        False,
    )
    corr = fdsvrg._lazy_corrections(bd, n, u, "proba")
    mask = jnp.ones(1, dtype=jnp.float32)
    d_sum = np.zeros(d, np.float64)
    p_sum = np.zeros(d, np.float64)
    for k in range(draws):
        samples = jnp.asarray(
            rng.integers(0, n, size=(1, u)).astype(np.int32)
        )
        dense = fdsvrg._inner_epoch(
            bd.indices, bd.values, data.labels, w0, z, s0, samples, eta,
            mask, "logistic", reg.name, reg.lam, bd.block_dims, False,
        )
        proba = fdsvrg._lazy_inner_epoch(
            bd.indices, bd.values, data.labels, w0, z, s0, samples, eta,
            mask, corr, "logistic", reg.name, reg.lam, bd.block_dims,
            False, "proba",
        )
        d_sum += np.asarray(dense, np.float64) - np.asarray(w0, np.float64)
        p_sum += np.asarray(proba, np.float64) - np.asarray(w0, np.float64)
    mean_dense = d_sum / draws
    mean_proba = p_sum / draws
    # CLT tolerance: the proba update per draw is O(corr * eta * decay);
    # 512 draws shrink the sampling noise ~23x below that scale.
    scale = float(np.abs(mean_dense).max())
    np.testing.assert_allclose(
        mean_proba, mean_dense, atol=max(scale, 1e-4) * 0.35
    )
    # and the bias really is small relative to the mean update magnitude
    err = np.abs(mean_proba - mean_dense).mean()
    assert err <= max(np.abs(mean_dense).mean(), 1e-6)


@pytest.mark.slow
def test_proba_end_to_end_news20_converges():
    """The unbiased variant must actually optimize on the real preset: a
    quick news20 run through the front door, final objective within a
    loose rtol of the eager path.  The rtol is honest about the price of
    the estimator: news20's columns are stored by ~1 row each, so the
    corrections are ~N and the per-touch decay variance is large — the
    proba run tracks the eager objective to ~7-9 % here (measured across
    seeds 1/5/11/23), while genuinely descending.  It is a different
    stochastic estimator, not a bit-identical one; bit-level claims
    belong to the exact variant only."""
    from repro.api import ExperimentSpec, solve

    base = dict(method="serial", dataset="news20", reg=losses.l2(1e-4),
                eta=0.05, inner_steps=998, outer_iters=4, seed=5)
    a = solve(ExperimentSpec(**base))
    b = solve(ExperimentSpec(lazy_updates="proba", **base))
    fa, fb = a.final_objective(), b.final_objective()
    assert np.isfinite(fb)
    assert abs(fa - fb) <= 0.15 * abs(fa)
    # and it descended from the start
    assert fb < a.history[0].objective


# ---------------------------------------------------------------------------
# validation surfaces
# ---------------------------------------------------------------------------


def test_check_lazy_rejects_unknown_variant():
    with pytest.raises(ValueError, match="lazy_updates"):
        _check_lazy("bogus")
    data = _driver_data()
    cfg = SVRGConfig(eta=0.1, inner_steps=4, outer_iters=1)
    with pytest.raises(ValueError, match="lazy_updates"):
        run_serial_svrg(data, losses.logistic, losses.no_reg(), cfg,
                        lazy_updates="bogus")


def test_spec_and_registry_validation():
    from repro.api import ExperimentSpec, method_info, solve

    data = _driver_data()
    with pytest.raises(ValueError, match="lazy_updates"):
        ExperimentSpec(method="serial", data=data, lazy_updates="nope")
    # capability mismatch fails loudly in solve(), not silently
    with pytest.raises(ValueError, match="does not support lazy_updates"):
        solve(ExperimentSpec(method="dsvrg", data=data, lazy_updates="exact",
                             outer_iters=1, inner_steps=4))
    for name in ("serial", "fdsvrg", "fdsvrg_sim"):
        assert method_info(name).supports_lazy
    for name in ("dsvrg", "synsvrg", "asysvrg", "pslite_sgd",
                 "fdsvrg_sharded"):
        assert not method_info(name).supports_lazy


def test_solve_lazy_exact_bitwise_through_front_door():
    from repro.api import ExperimentSpec, solve

    data = _driver_data()
    base = dict(data=data, reg=losses.l1(1e-3), outer_iters=2,
                inner_steps=10, eta=0.1, q=1)
    for method in ("serial", "fdsvrg", "fdsvrg_sim"):
        a = solve(ExperimentSpec(method=method, **base))
        b = solve(ExperimentSpec(method=method, lazy_updates="exact", **base))
        np.testing.assert_array_equal(_bits(a.w), _bits(b.w), err_msg=method)
