"""FD-Prox-SVRG correctness (paper eq. 3: g decomposes over feature blocks,
so the prox step is purely block-local and communication-free).

Covers:
  * prox operators: soft-threshold analytic identity + hypothesis
    properties, elastic-net closed form via its optimality condition;
  * the four implementations (serial, metered FD, worker simulation,
    shard_map) agree on L1 / elastic-net problems, jnp and kernel paths
    bit-identical;
  * L1 runs produce genuinely sparse iterates while the comm-scalar
    meter equals the L2 path exactly (the prox adds zero traffic);
  * recorded grad_norm is the prox gradient-mapping norm at the recorded
    iterate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses
from repro.core.fdsvrg import (
    SVRGConfig,
    fdsvrg_worker_simulation,
    full_gradient,
    optimality_norm,
    run_fdsvrg,
    run_serial_svrg,
)
from repro.core import baselines
from repro.core.partition import balanced
from repro.data.synthetic import make_sparse_classification
from repro.dist import make_mesh

try:
    import hypothesis  # noqa: F401  (dev-only dep; see requirements-dev.txt)

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


LOSS = losses.logistic

L1 = losses.l1(2e-3)
EN = losses.elastic_net(2e-3, 1e-3)
REGS = pytest.mark.parametrize("reg", [L1, EN], ids=["l1", "elastic_net"])


@pytest.fixture(scope="module")
def tiny_data():
    return make_sparse_classification(
        dim=512, num_instances=96, nnz_per_instance=12, seed=3
    )


# ---------------------------------------------------------------------------
# prox operators
# ---------------------------------------------------------------------------


def test_soft_threshold_matches_analytic():
    v = jnp.asarray(np.linspace(-2.0, 2.0, 41).astype(np.float32))
    t = 0.3
    got = np.asarray(losses.soft_threshold(v, t))
    vn = np.asarray(v)
    want = np.where(vn > t, vn - t, np.where(vn < -t, vn + t, 0.0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_prox_l1_is_soft_threshold():
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=64).astype(np.float32))
    eta = 0.25
    got = L1.prox(v, eta)
    want = losses.soft_threshold(v, eta * L1.lam)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prox_identity_for_smooth_family():
    rng = np.random.default_rng(1)
    v = jnp.asarray(rng.normal(size=32).astype(np.float32))
    for reg in (losses.l2(0.1), losses.no_reg()):
        np.testing.assert_array_equal(np.asarray(reg.prox(v, 0.5)), np.asarray(v))


def test_elastic_net_prox_optimality_condition():
    """x = prox_{eta g}(v) iff 0 in lam1*d|x| + lam2*x + (x - v)/eta."""
    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.normal(size=256).astype(np.float32))
    eta, lam1, lam2 = 0.4, 0.3, 0.2
    reg = losses.elastic_net(lam1, lam2)
    x = np.asarray(reg.prox(v, eta))
    vn = np.asarray(v)
    nz = x != 0.0
    # nonzero coords: lam1*sign(x) + lam2*x + (x - v)/eta == 0
    resid = lam1 * np.sign(x[nz]) + lam2 * x[nz] + (x[nz] - vn[nz]) / eta
    np.testing.assert_allclose(resid, 0.0, atol=1e-5)
    # zero coords: |v|/eta <= lam1  (subdifferential of |.| is [-1, 1])
    assert np.all(np.abs(vn[~nz]) <= eta * lam1 + 1e-6)
    # and the prox genuinely thresholds: some coordinates hit zero
    assert np.any(~nz) and np.any(nz)


def test_elastic_net_value_and_grad():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=32).astype(np.float32))
    w = jnp.where(jnp.abs(w) < 1e-3, 0.1, w)  # avoid the |.| kink
    reg = losses.elastic_net(0.05, 0.1)
    want = 0.05 * jnp.sum(jnp.abs(w)) + 0.5 * 0.1 * jnp.sum(w * w)
    np.testing.assert_allclose(float(reg.value(w)), float(want), rtol=1e-6)
    g = jax.grad(reg.value)(w)
    np.testing.assert_allclose(
        np.asarray(reg.grad(w)), np.asarray(g), rtol=1e-5, atol=1e-6
    )


if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @given(
        st.integers(min_value=1, max_value=128),
        st.floats(min_value=0.0, max_value=2.0),
        st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=30, deadline=None)
    def test_property_soft_threshold_analytic(n, t, seed):
        rng = np.random.default_rng(seed)
        v = rng.normal(scale=2.0, size=n).astype(np.float32)
        got = np.asarray(losses.soft_threshold(jnp.asarray(v), t))
        want = np.sign(v) * np.maximum(np.abs(v) - t, 0.0)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        # shrinkage properties
        assert np.all(np.abs(got) <= np.abs(v))  # never grows a coordinate
        assert np.all(got[np.abs(v) <= t] == 0.0)  # dead zone
        assert np.all(got * v >= 0.0)  # never flips sign

    @given(
        st.floats(min_value=1e-3, max_value=1.0),
        st.floats(min_value=0.0, max_value=1.0),
        st.floats(min_value=0.0, max_value=1.0),
        st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=30, deadline=None)
    def test_property_prox_is_nonexpansive(eta, lam1, lam2, seed):
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.normal(size=64).astype(np.float32))
        b = jnp.asarray(rng.normal(size=64).astype(np.float32))
        reg = losses.elastic_net(lam1, lam2)
        pa, pb = np.asarray(reg.prox(a, eta)), np.asarray(reg.prox(b, eta))
        assert np.linalg.norm(pa - pb) <= np.linalg.norm(
            np.asarray(a) - np.asarray(b)
        ) * (1 + 1e-6)


# ---------------------------------------------------------------------------
# the four implementations agree (FD-Prox-SVRG == serial Prox-SVRG)
# ---------------------------------------------------------------------------


@REGS
@pytest.mark.parametrize("q", [2, 4, 7])
def test_fd_prox_svrg_equals_serial(tiny_data, reg, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=24, outer_iters=3, seed=11)
    serial = run_serial_svrg(tiny_data, LOSS, reg, cfg)
    fd = run_fdsvrg(tiny_data, balanced(tiny_data.dim, q), LOSS, reg, cfg)
    np.testing.assert_allclose(
        np.asarray(fd.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )


@REGS
@pytest.mark.parametrize("q", [2, 5])
def test_prox_worker_simulation_equals_serial(tiny_data, reg, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=12, outer_iters=2, seed=7)
    serial = run_serial_svrg(tiny_data, LOSS, reg, cfg)
    sim = fdsvrg_worker_simulation(
        tiny_data, balanced(tiny_data.dim, q), LOSS, reg, cfg
    )
    np.testing.assert_allclose(
        np.asarray(sim.w), np.asarray(serial.w), rtol=2e-4, atol=2e-6
    )
    assert sim.meter.total_scalars > 0


@REGS
@pytest.mark.parametrize("q", [2, 4])
def test_prox_use_kernels_bit_identical(tiny_data, reg, q):
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, batch_size=2, seed=5)
    part = balanced(tiny_data.dim, q)
    a = run_fdsvrg(tiny_data, part, LOSS, reg, cfg, use_kernels=False)
    b = run_fdsvrg(tiny_data, part, LOSS, reg, cfg, use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    assert a.meter.total_scalars == b.meter.total_scalars
    sa = fdsvrg_worker_simulation(tiny_data, part, LOSS, reg, cfg,
                                  use_kernels=False)
    sb = fdsvrg_worker_simulation(tiny_data, part, LOSS, reg, cfg,
                                  use_kernels=True)
    np.testing.assert_array_equal(np.asarray(sa.w), np.asarray(sb.w))


@REGS
def test_prox_option_II_and_minibatch(tiny_data, reg):
    """Option II's masked tail steps (eta_m = 0 => threshold 0 => identity)
    and u > 1 must survive the prox path, jnp and kernel alike."""
    cfg = SVRGConfig(eta=0.2, inner_steps=16, outer_iters=2, batch_size=4,
                     option="II", seed=3)
    a = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, reg, cfg,
                   use_kernels=False)
    b = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, reg, cfg,
                   use_kernels=True)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


@pytest.mark.parametrize("reg_name,lam,lam2", [
    ("l1", 2e-3, 0.0), ("elastic_net", 2e-3, 1e-3),
])
@pytest.mark.parametrize("use_kernels", [False, True])
def test_prox_shardmap_matches_serial_reference(reg_name, lam, lam2, use_kernels):
    """The deployable shard_map worker runs the same prox update: identical
    iterates to the serial reference under a shared sample stream."""
    from repro.core.fdsvrg import _full_grad_blocks, _inner_epoch
    from repro.core.fdsvrg_shardmap import (
        FDSVRGShardedConfig, make_fullgrad, make_inner_epoch,
    )
    from repro.data.block_csr import BlockCSR

    data = make_sparse_classification(
        dim=384, num_instances=48, nnz_per_instance=8, seed=3
    )
    eta, inner, outers, u = 0.2, 12, 2, 2
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=eta, inner_steps=inner, batch_size=u,
        reg_name=reg_name, lam=lam, lam2=lam2, use_kernels=use_kernels,
    )
    fullgrad = make_fullgrad(mesh, cfg, feature_axes=("model",))
    inner_epoch = make_inner_epoch(mesh, cfg, feature_axes=("model",))
    block = BlockCSR.from_padded(data, balanced(data.dim, 1))
    placed = block.on_mesh(mesh, ("model",))

    rng = np.random.default_rng(5)
    all_samples = [
        rng.integers(0, data.num_instances, size=(inner, u)).astype(np.int32)
        for _ in range(outers)
    ]
    w = jnp.zeros((data.dim,), jnp.float32)
    for t in range(outers):
        z, s0 = fullgrad(w, placed.groups, placed.labels)
        w = inner_epoch(w, z, s0, placed.indices, placed.values, placed.labels,
                        jnp.asarray(all_samples[t]))

    w_ref = jnp.zeros((data.dim,), jnp.float32)
    for t in range(outers):
        z, s0 = _full_grad_blocks(
            block.groups, data.labels, w_ref,
            "logistic", block.block_dims, False,
        )
        w_ref = _inner_epoch(
            block.indices, block.values, data.labels, w_ref, z, s0,
            jnp.asarray(all_samples[t]), eta, jnp.ones(inner, jnp.float32),
            "logistic", reg_name, lam, block.block_dims, False, lam2=lam2,
        )
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(w_ref), rtol=2e-4, atol=2e-6
    )


# ---------------------------------------------------------------------------
# sparsity + communication: the paper's point — prox is free
# ---------------------------------------------------------------------------


def test_l1_run_produces_sparse_iterates_and_same_comm(tiny_data):
    """L1 ends with genuinely sparse w (nnz(w) < d, unlike the historical
    sign-subgradient path) while the comm-scalar meter equals the L2 run
    exactly: the prox is block-local, zero extra traffic."""
    cfg = SVRGConfig(eta=0.25, inner_steps=96, outer_iters=4, seed=1)
    part = balanced(tiny_data.dim, 4)
    l1 = run_fdsvrg(tiny_data, part, LOSS, losses.l1(2e-3), cfg)
    l2 = run_fdsvrg(tiny_data, part, LOSS, losses.l2(2e-3), cfg)

    w1 = np.asarray(l1.w)
    nnz = int(np.count_nonzero(w1))
    assert 0 < nnz < tiny_data.dim  # sparse, but not trivially zero
    # the subgradient path could only ever produce exact zeros by accident;
    # the prox zeroes entire dead-zone coordinates
    assert nnz < int(np.count_nonzero(np.asarray(l2.w)))

    assert l1.meter.total_scalars == l2.meter.total_scalars
    assert l1.meter.total_rounds == l2.meter.total_rounds
    assert np.isfinite(l1.final_objective())
    assert l1.history[-1].objective < l1.history[0].objective


def test_elastic_net_sparser_with_larger_l1(tiny_data):
    cfg = SVRGConfig(eta=0.25, inner_steps=96, outer_iters=3, seed=1)
    part = balanced(tiny_data.dim, 2)
    small = run_fdsvrg(tiny_data, part, LOSS, losses.elastic_net(5e-4, 1e-3), cfg)
    big = run_fdsvrg(tiny_data, part, LOSS, losses.elastic_net(8e-3, 1e-3), cfg)
    assert int(np.count_nonzero(np.asarray(big.w))) < int(
        np.count_nonzero(np.asarray(small.w))
    )


def test_prox_baselines_run_l1(tiny_data):
    """The PS baselines accept the prox family too (like-for-like Fig 6/7
    comparisons)."""
    cfg = SVRGConfig(eta=0.1, inner_steps=32, outer_iters=3, seed=0)
    for runner in (baselines.run_dsvrg, baselines.run_syn_svrg,
                   baselines.run_asy_svrg):
        res = runner(tiny_data, 4, LOSS, L1, cfg)
        assert np.isfinite(res.history[-1].objective)
        assert res.history[-1].objective < res.history[0].objective
        assert int(np.count_nonzero(np.asarray(res.w))) < tiny_data.dim


# ---------------------------------------------------------------------------
# reporting: gradient-mapping norm at the recorded iterate
# ---------------------------------------------------------------------------


def test_prox_grad_norm_is_gradient_mapping_at_recorded_iterate(tiny_data):
    cfg = SVRGConfig(eta=0.2, inner_steps=24, outer_iters=2, seed=9)
    res = run_fdsvrg(tiny_data, balanced(tiny_data.dim, 4), LOSS, L1, cfg)
    gd, _ = full_gradient(tiny_data, res.w, LOSS)
    want = optimality_norm(gd, res.w, L1, cfg.eta)
    np.testing.assert_allclose(res.history[-1].grad_norm, want, rtol=1e-4)


def test_optimality_norm_reduces_to_grad_norm_when_smooth(tiny_data):
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=tiny_data.dim).astype(np.float32))
    gd, _ = full_gradient(tiny_data, w, LOSS)
    reg = losses.l2(1e-3)
    want = float(jnp.linalg.norm(gd + reg.grad(w)))
    assert optimality_norm(gd, w, reg, 0.2) == want


def test_optimality_norm_vanishes_near_prox_fixed_point(tiny_data):
    """Run long enough that the gradient mapping is far below its initial
    value — the measure actually tracks composite optimality."""
    cfg = SVRGConfig(eta=0.25, inner_steps=96, outer_iters=12, seed=0)
    res = run_serial_svrg(tiny_data, LOSS, L1, cfg)
    norms = [h.grad_norm for h in res.history]
    assert norms[-1] < 0.35 * norms[0]
