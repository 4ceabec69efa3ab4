"""Faithfulness tests for the paper's algorithm.

The load-bearing claims:
  1. FD-SVRG's update sequence == serial SVRG's (paper §4.3: "exactly
     equivalent"), for any feature partition.
  2. Communication accounting matches the closed forms of §4.5
     (2qN-per-N-gradients for FD-SVRG, 2qd+2d per outer for DSVRG, ...).
  3. Theorem 1: linear convergence of Option I on a strongly convex
     problem, with empirical rate within the theorem's bound.
  4. FD-SVRG communicates less than DSVRG iff roughly d > N (the paper's
     headline claim).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses
from repro.core.comm import ClusterModel, CommMeter
from repro.core.fdsvrg import (
    SVRGConfig,
    fdsvrg_worker_simulation,
    full_gradient,
    objective,
    run_fdsvrg,
    run_serial_svrg,
)
from repro.core.partition import balanced, by_nnz, feature_counts
from repro.core import baselines
from repro.data.synthetic import make_sparse_classification


@pytest.fixture(scope="module")
def tiny_data():
    return make_sparse_classification(
        dim=512, num_instances=96, nnz_per_instance=12, seed=3
    )


@pytest.fixture(scope="module")
def small_data():
    return make_sparse_classification(
        dim=4096, num_instances=256, nnz_per_instance=24, seed=0
    )


LOSS = losses.logistic
REG = losses.l2(1e-3)


# ---------------------------------------------------------------------------
# 1. Exact equivalence with serial SVRG
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [1, 2, 4, 7, 8])
def test_fdsvrg_equals_serial_svrg(tiny_data, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=32, outer_iters=3, seed=11)
    serial = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    part = balanced(tiny_data.dim, q)
    fd = run_fdsvrg(tiny_data, part, LOSS, REG, cfg)
    np.testing.assert_allclose(
        np.asarray(fd.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )


@pytest.mark.parametrize("q", [2, 4, 5])
def test_worker_simulation_equals_serial(tiny_data, q):
    """The object-level simulation — workers only touch their own blocks —
    reproduces the serial iterates."""
    cfg = SVRGConfig(eta=0.2, inner_steps=12, outer_iters=2, seed=7)
    serial = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    part = balanced(tiny_data.dim, q)
    sim = fdsvrg_worker_simulation(tiny_data, part, LOSS, REG, cfg)
    np.testing.assert_allclose(
        np.asarray(sim.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )
    assert sim.meter.total_scalars > 0
    # the sim is a full harness citizen now: same-iterate reporting too
    np.testing.assert_allclose(
        sim.history[-1].objective, serial.history[-1].objective, rtol=1e-5
    )


def test_fdsvrg_nnz_partition_equals_serial(tiny_data):
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, seed=5)
    counts = feature_counts(
        np.asarray(tiny_data.indices), np.asarray(tiny_data.values), tiny_data.dim
    )
    part = by_nnz(tiny_data.dim, 4, counts)
    serial = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    fd = run_fdsvrg(tiny_data, part, LOSS, REG, cfg)
    np.testing.assert_allclose(
        np.asarray(fd.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )


def test_minibatch_variant_consistent(tiny_data):
    """u>1 (paper §4.4.1) must agree between FD and serial paths too."""
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, batch_size=4, seed=9)
    serial = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    fd = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, REG, cfg)
    np.testing.assert_allclose(
        np.asarray(fd.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )


def test_option_II_runs_and_converges(tiny_data):
    cfg = SVRGConfig(eta=0.2, inner_steps=32, outer_iters=4, option="II", seed=1)
    res = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    assert res.history[-1].objective < res.history[0].objective


# ---------------------------------------------------------------------------
# 1b. Fused-kernel path ≡ reference path (bit-identical, interpret mode)
# ---------------------------------------------------------------------------


def test_serial_use_kernels_bit_identical(tiny_data):
    cfg = SVRGConfig(eta=0.2, inner_steps=24, outer_iters=2, batch_size=2, seed=11)
    a = run_serial_svrg(tiny_data, LOSS, REG, cfg, use_kernels=False)
    b = run_serial_svrg(tiny_data, LOSS, REG, cfg, use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


@pytest.mark.parametrize("q", [2, 4, 7])
def test_fdsvrg_use_kernels_bit_identical(tiny_data, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, batch_size=2, seed=5)
    part = balanced(tiny_data.dim, q)
    a = run_fdsvrg(tiny_data, part, LOSS, REG, cfg, use_kernels=False)
    b = run_fdsvrg(tiny_data, part, LOSS, REG, cfg, use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    # metering must be layout- and kernel-independent
    assert a.meter.total_scalars == b.meter.total_scalars
    # and the kernel path still matches serial within tolerance
    serial = run_serial_svrg(tiny_data, LOSS, REG, cfg)
    np.testing.assert_allclose(
        np.asarray(b.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )


@pytest.mark.parametrize("q", [2, 5])
def test_worker_simulation_use_kernels_bit_identical(tiny_data, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=8, outer_iters=2, seed=7)
    part = balanced(tiny_data.dim, q)
    a = fdsvrg_worker_simulation(tiny_data, part, LOSS, REG, cfg,
                                 use_kernels=False)
    b = fdsvrg_worker_simulation(tiny_data, part, LOSS, REG, cfg,
                                 use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


def test_use_kernels_option_II_and_minibatch(tiny_data):
    """Option II's masked tail steps and u>1 must survive the fused path."""
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, batch_size=4,
                     option="II", seed=3)
    a = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, REG, cfg,
                   use_kernels=False)
    b = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, REG, cfg,
                   use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


@pytest.mark.parametrize(
    "reg",
    [losses.l1(1e-3), losses.elastic_net(1e-3, 1e-4), losses.no_reg()],
    ids=["l1", "elastic_net", "none"],
)
def test_use_kernels_accepts_whole_regularizer_family(reg):
    """The historical `_kernel_lam` L2-only ValueError is gone: the fused
    prox kernel covers l1 / elastic-net / none, bit-identical to jnp."""
    data = make_sparse_classification(
        dim=128, num_instances=16, nnz_per_instance=4, seed=0
    )
    cfg = SVRGConfig(eta=0.1, inner_steps=6, outer_iters=2)
    a = run_serial_svrg(data, LOSS, reg, cfg, use_kernels=False)
    b = run_serial_svrg(data, LOSS, reg, cfg, use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


def test_fdsvrg_accepts_prebuilt_block_data(tiny_data):
    from repro.data.block_csr import BlockCSR

    part = balanced(tiny_data.dim, 4)
    block_data = BlockCSR.from_padded(tiny_data, part)
    cfg = SVRGConfig(eta=0.2, inner_steps=8, outer_iters=1, seed=1)
    a = run_fdsvrg(tiny_data, part, LOSS, REG, cfg, block_data=block_data)
    b = run_fdsvrg(tiny_data, part, LOSS, REG, cfg)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    with pytest.raises(ValueError, match="partition"):
        run_fdsvrg(tiny_data, balanced(tiny_data.dim, 2), LOSS, REG, cfg,
                   block_data=block_data)


# ---------------------------------------------------------------------------
# 1c. grad_norm regression: recorded norm is the gradient AT the recorded
# iterate, not the stale snapshot pair the drivers used to report
# ---------------------------------------------------------------------------


def _expected_grad_norm(data, w, reg):
    gd, _ = full_gradient(data, w, losses.logistic)
    return float(jnp.linalg.norm(gd + reg.grad(w)))


@pytest.mark.parametrize("outers", [1, 2])
@pytest.mark.parametrize(
    "runner",
    [
        lambda d, cfg: run_serial_svrg(d, LOSS, REG, cfg),
        lambda d, cfg: run_fdsvrg(d, balanced(d.dim, 4), LOSS, REG, cfg),
        lambda d, cfg: baselines.run_dsvrg(d, 4, LOSS, REG, cfg),
        lambda d, cfg: baselines.run_syn_svrg(d, 4, LOSS, REG, cfg),
        lambda d, cfg: baselines.run_asy_svrg(d, 4, LOSS, REG, cfg),
    ],
    ids=["serial", "fdsvrg", "dsvrg", "synsvrg", "asysvrg"],
)
def test_grad_norm_recorded_at_post_epoch_iterate(tiny_data, runner, outers):
    """history[-1].grad_norm must equal an independently computed
    ||grad f(w_history)|| at the returned iterate (the historical code mixed
    the snapshot z with the post-epoch w — the norm of nothing)."""
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=outers, seed=13)
    res = runner(tiny_data, cfg)
    want = _expected_grad_norm(tiny_data, res.w, REG)
    got = res.history[-1].grad_norm
    # blockwise (tree-order) vs global float summation differ in the last
    # bits only
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_grad_norm_every_record_consistent(tiny_data):
    """Each record's grad_norm corresponds to that outer's post-epoch w:
    truncated reruns (same seed => same iterate prefix) agree record-for-
    record with the longer run."""
    cfg3 = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=3, seed=2)
    full = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, REG, cfg3)
    for outers in (1, 2):
        cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=outers, seed=2)
        part = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, REG, cfg)
        assert part.history[-1].grad_norm == full.history[outers - 1].grad_norm
        np.testing.assert_allclose(
            part.history[-1].grad_norm,
            _expected_grad_norm(tiny_data, part.w, REG),
            rtol=1e-4,
        )


# ---------------------------------------------------------------------------
# 2. Communication accounting (paper §4.5)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [2, 4, 8, 16])
def test_fdsvrg_comm_closed_form(tiny_data, q):
    m, outers, u = 20, 2, 1
    cfg = SVRGConfig(eta=0.1, inner_steps=m, outer_iters=outers, batch_size=u)
    fd = run_fdsvrg(tiny_data, balanced(tiny_data.dim, q), LOSS, REG, cfg)
    n = tiny_data.num_instances
    # per outer: full-grad tree on the N-vector (2qN) + M trees on u scalars.
    expected = outers * (2 * q * n + 2 * q * u * m)
    assert fd.meter.total_scalars == expected


def test_dsvrg_comm_closed_form(tiny_data):
    q, outers = 4, 3
    cfg = SVRGConfig(eta=0.1, inner_steps=tiny_data.num_instances // q, outer_iters=outers)
    res = baselines.run_dsvrg(tiny_data, q, LOSS, REG, cfg)
    d = tiny_data.dim
    expected = outers * (2 * q * d + 2 * d)  # paper §4.5
    assert res.meter.total_scalars == expected


def test_comm_crossover_d_vs_n():
    """FD-SVRG wins on scalars iff d > N (the paper's headline claim),
    comparing per-outer totals with the paper's M settings."""
    q = 8
    highdim = make_sparse_classification(
        dim=8192, num_instances=128, nnz_per_instance=8, seed=0
    )
    lowdim = make_sparse_classification(
        dim=128, num_instances=4096, nnz_per_instance=8, seed=0
    )
    for data, fd_should_win in ((highdim, True), (lowdim, False)):
        n = data.num_instances
        cfg_fd = SVRGConfig(eta=0.05, inner_steps=n, outer_iters=1)
        cfg_ds = SVRGConfig(eta=0.05, inner_steps=n // q, outer_iters=1)
        fd = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg_fd)
        ds = baselines.run_dsvrg(data, q, LOSS, REG, cfg_ds)
        if fd_should_win:
            assert fd.meter.total_scalars < ds.meter.total_scalars
        else:
            assert fd.meter.total_scalars > ds.meter.total_scalars


def test_ps_svrg_comm_dominates(tiny_data):
    """Parameter-server SVRG traffic is O(M·(qd + q·nnz)) per outer — far
    above both FD-SVRG and DSVRG on high-dim data (paper §4.5)."""
    q = 4
    cfg = SVRGConfig(eta=0.1, inner_steps=16, outer_iters=1)
    fd = run_fdsvrg(tiny_data, balanced(tiny_data.dim, q), LOSS, REG, cfg)
    syn = baselines.run_syn_svrg(tiny_data, q, LOSS, REG, cfg)
    asy = baselines.run_asy_svrg(tiny_data, q, LOSS, REG, cfg)
    assert syn.meter.total_scalars > fd.meter.total_scalars
    assert asy.meter.total_scalars > fd.meter.total_scalars


# ---------------------------------------------------------------------------
# 3. Convergence (Theorem 1)
# ---------------------------------------------------------------------------


def test_linear_convergence_rate(small_data):
    """Empirical per-outer contraction of the objective gap should be <= the
    Theorem-1 factor (a^M + b/(1-a)) once within the quadratic basin.
    Run in float64 so the gap doesn't hit the fp32 objective floor."""
    import dataclasses as _dc

    from repro.data.sparse import PaddedCSR

    lam = 0.1
    reg = losses.l2(lam)
    # Smoothness of f_i: phi'' <= 1/4 times ||x||^2 (rows are L2-normalized
    # so ||x||=1) plus lam from the regularizer; strong convexity >= lam.
    L = 0.25 + lam
    mu = lam
    # b/(1-a) = 2L^2 eta / (mu - 2L^2 eta) < 1 requires eta < mu/(4L^2);
    # take eta = mu/(8L^2) so b/(1-a) = 1/3 and a^M shrinks geometrically.
    eta = mu / (8 * L * L)
    M = small_data.num_instances
    a = 1 - mu * eta + 2 * L * L * eta * eta
    b = 2 * L * L * eta * eta
    bound = a**M + b / (1 - a)
    assert bound < 1.0

    with jax.enable_x64(True):
        data64 = PaddedCSR(
            indices=jnp.asarray(np.asarray(small_data.indices)),
            values=jnp.asarray(np.asarray(small_data.values), dtype=jnp.float64),
            labels=jnp.asarray(np.asarray(small_data.labels), dtype=jnp.float64),
            dim=small_data.dim,
        )
        cfg = SVRGConfig(eta=eta, inner_steps=M, outer_iters=25, seed=0)
        res = run_serial_svrg(data64, LOSS, reg, cfg)
        objs = res.objectives()
        # approximate f(w*) by running longer
        cfg_star = SVRGConfig(eta=eta, inner_steps=M, outer_iters=120, seed=1)
        star = run_serial_svrg(data64, LOSS, reg, cfg_star).final_objective()
    gaps = np.maximum(objs - star, 1e-16)
    # geometric decrease while the gap is informative (above f64 noise)
    informative = gaps > 5e-15
    ratios = np.array(
        [gaps[i + 1] / gaps[i] for i in range(len(gaps) - 1)
         if informative[i] and informative[i + 1]]
    )
    assert len(ratios) >= 3, f"gaps collapsed too fast: {gaps[:8]}"
    assert np.median(ratios) < 1.0  # strictly contracting
    # and the contraction is at least as good as the theorem's bound
    assert np.median(ratios) <= bound + 0.05


def test_fdsvrg_decreases_objective(small_data):
    cfg = SVRGConfig(eta=0.25, inner_steps=small_data.num_instances, outer_iters=5)
    res = run_fdsvrg(small_data, balanced(small_data.dim, 8), LOSS, REG, cfg)
    objs = res.objectives()
    assert objs[-1] <= objs[0]
    assert objs[-1] < 0.693 * 0.55  # far below the w=0 objective log(2)
    assert np.all(np.isfinite(objs))


# ---------------------------------------------------------------------------
# 4. Baselines converge (sanity for the benchmark suite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "runner,kwargs",
    [
        (baselines.run_dsvrg, {}),
        (baselines.run_syn_svrg, {}),
        (baselines.run_asy_svrg, {}),
    ],
)
def test_baselines_converge(tiny_data, runner, kwargs):
    cfg = SVRGConfig(eta=0.1, inner_steps=48, outer_iters=4)
    res = runner(tiny_data, 4, LOSS, REG, cfg, **kwargs)
    assert res.history[-1].objective < res.history[0].objective
    assert np.isfinite(res.history[-1].objective)


def test_pslite_sgd_converges_slowly(tiny_data):
    """Fixed-step async SGD stalls at its noise floor while AsySVRG keeps
    contracting — the reason the paper builds on SVRG (Tables 2-3)."""
    cfg = SVRGConfig(eta=0.1, inner_steps=256, outer_iters=6)
    sgd = baselines.run_pslite_sgd(tiny_data, 4, LOSS, REG, cfg)
    svrg = baselines.run_asy_svrg(tiny_data, 4, LOSS, REG, cfg)
    assert np.isfinite(sgd.history[-1].objective)
    assert sgd.history[-1].objective < sgd.history[0].objective + 1e-6  # moves
    # and VR beats plain SGD at equal gradient budget
    assert svrg.history[-1].objective <= sgd.history[-1].objective + 1e-4


def test_modeled_time_ordering():
    """Figure 6's qualitative ordering under the cluster model: in the
    paper's regime (d >> N, mini-batched inner loop per §4.4.1), FD-SVRG
    reaches the same gradient budget in less modeled time than DSVRG."""
    data = make_sparse_classification(
        dim=65536, num_instances=256, nnz_per_instance=24, seed=2
    )
    q, u = 8, 32
    n = data.num_instances
    # equal gradient budgets: FD does n grads/outer via n/u batched steps;
    # DSVRG does n/q grads/outer on one machine (paper M = N/q).
    cfg_fd = SVRGConfig(eta=0.25, inner_steps=n // u, outer_iters=3, batch_size=u)
    cfg_ds = SVRGConfig(eta=0.25, inner_steps=n // q, outer_iters=3)
    fd = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg_fd)
    ds = baselines.run_dsvrg(data, q, LOSS, REG, cfg_ds)
    assert fd.history[-1].modeled_time_s < ds.history[-1].modeled_time_s
    # and DSVRG in turn beats the parameter-server SVRG (paper Figure 6)
    cfg_ps = SVRGConfig(eta=0.25, inner_steps=n // q, outer_iters=3)
    ps = baselines.run_syn_svrg(data, q, LOSS, REG, cfg_ps)
    assert ds.history[-1].modeled_time_s < ps.history[-1].modeled_time_s
