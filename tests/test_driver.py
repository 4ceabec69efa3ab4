"""The outer-loop harness and the single §4.5 cost model.

Load-bearing properties after the driver-drift refactor:

1. **Drift guard** — for every method, the measured-sim meter (what the
   driver actually records per outer) and the analytic schedule
   (``benchmarks.common.analytic_outer`` → ``repro.dist.COSTS``) agree on
   scalars-per-outer exactly, and on modeled seconds to float precision.
   A new driver or a edited closed form that drifts breaks this test, not
   a benchmark three PRs later.
2. **Harness semantics** — snapshot rotation (one extra full gradient per
   run, post-epoch z/w pairs), same-iterate reporting for every driver
   including PS-Lite, and the shared rng-stream conventions.
3. Satellites: the `_inner_epoch` recompile fix (lam traced) and
   `use_kernels` plumbed through run_method.  (The BlockCSR cache tests
   moved to tests/test_api.py with the cache itself — it now lives in
   repro.api.cache.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, losses
from repro.core.driver import (
    OuterRecord,
    RunResult,
    optimality_norm,
    run_outer_loop,
)
from repro.core.fdsvrg import (
    SVRGConfig,
    _inner_epoch,
    full_gradient,
    fdsvrg_worker_simulation,
    run_fdsvrg,
    run_serial_svrg,
)
from repro.core.partition import balanced
from repro.data.synthetic import make_sparse_classification
from repro.dist import COSTS, ClusterModel, make_mesh

LOSS = losses.logistic
REG = losses.l2(1e-3)


@pytest.fixture(scope="module")
def data():
    # n divisible by q and u so the paper-M conventions are exact integers.
    return make_sparse_classification(
        dim=512, num_instances=48, nnz_per_instance=8, seed=2
    )


def _spec_of(data):
    """A DatasetSpec-shaped view of a synthetic set, for analytic_outer."""
    from repro.data.datasets import DatasetSpec

    return DatasetSpec("synthetic", data.dim, data.num_instances,
                       int(data.nnz_max), 4)


# ---------------------------------------------------------------------------
# 1. the drift guard: measured meter == analytic schedule, per outer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [2, 4])
@pytest.mark.parametrize(
    "method", ["fdsvrg", "serial", "dsvrg", "synsvrg", "asysvrg", "pslite_sgd"]
)
def test_measured_meter_matches_analytic_schedule(data, method, q):
    """Run each driver at the paper's M convention and assert its meter
    and modeled time equal ``analytic_outer``'s closed form exactly —
    the same CostModel on both sides, by construction AND by measurement."""
    from benchmarks.common import analytic_outer

    n = data.num_instances
    outers, u = 2, 2
    cluster = ClusterModel()
    spec = _spec_of(data)

    if method == "fdsvrg":
        cfg = SVRGConfig(eta=0.2, inner_steps=n // u, outer_iters=outers,
                         batch_size=u)
        res = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg, cluster)
        t1, c1 = analytic_outer(method, spec, q, u=u, cluster=cluster)
    elif method == "serial":
        cfg = SVRGConfig(eta=0.2, inner_steps=n, outer_iters=outers)
        res = run_serial_svrg(data, LOSS, REG, cfg)
        t1, c1 = analytic_outer(method, spec, q, u=1, cluster=cluster)
    else:
        m = n // q if method in ("dsvrg", "synsvrg") else n
        cfg = SVRGConfig(eta=0.1, inner_steps=m, outer_iters=outers)
        runner = {
            "dsvrg": baselines.run_dsvrg,
            "synsvrg": baselines.run_syn_svrg,
            "asysvrg": baselines.run_asy_svrg,
            "pslite_sgd": baselines.run_pslite_sgd,
        }[method]
        res = runner(data, q, LOSS, REG, cfg, cluster)
        t1, c1 = analytic_outer(method, spec, q, cluster=cluster)

    assert res.meter.total_scalars == outers * c1
    if method == "serial":
        assert res.history[-1].modeled_time_s == 0.0  # serial: no backend
    else:
        np.testing.assert_allclose(
            res.history[-1].modeled_time_s, outers * t1, rtol=1e-12
        )
    # and per-record: the meter is cumulative outer by outer
    for h in res.history:
        assert h.comm_scalars == (h.outer + 1) * c1


@pytest.mark.parametrize("q", [2, 4])
@pytest.mark.parametrize("method", ["fd_saga", "fd_bcd"])
def test_update_rule_meter_matches_analytic_schedule(data, method, q):
    """The update-rule methods meter against the same closed forms: every
    FD-SAGA/FD-BCD scalar the backend records equals the analytic
    schedule (including fd_saga's one-time table-init phase, which the
    schedule carries as an offset — ``CostModel.init_cost``)."""
    from benchmarks.common import analytic_outer
    from repro.data.block_csr import BlockCSR
    from repro.dist import SimBackend
    from repro.optim.update_rules import (
        BCDRule,
        SAGARule,
        make_context,
        run_with_rule,
    )

    n = data.num_instances
    outers, u = 2, 2
    cluster = ClusterModel()
    spec = _spec_of(data)
    block = BlockCSR.from_padded(data, balanced(data.dim, q))
    if method == "fd_saga":
        cfg = SVRGConfig(eta=0.2, inner_steps=n // u, outer_iters=outers,
                         batch_size=u)
        rule = SAGARule()
    else:
        # One cycle over the q blocks per outer (the paper-M convention
        # registered as inner_rule="q").
        cfg = SVRGConfig(eta=0.2, inner_steps=q, outer_iters=outers)
        rule = BCDRule()
    ctx = make_context(block, LOSS, REG, cfg, backend=SimBackend(q, cluster))
    res = run_with_rule(rule, ctx)

    t1, c1 = analytic_outer(method, spec, q, u=u, cluster=cluster)
    t0, c0 = COSTS.init_cost(
        method, n=n, nnz=int(data.nnz_max), q=q, cluster=cluster
    )
    assert res.meter.total_scalars == c0 + outers * c1
    np.testing.assert_allclose(
        res.history[-1].modeled_time_s, t0 + outers * t1, rtol=1e-12
    )
    for h in res.history:
        assert h.comm_scalars == c0 + (h.outer + 1) * c1


@pytest.mark.parametrize("lazy", ["exact", "proba"])
def test_lazy_updates_comm_parity_with_eager_and_analytic(data, lazy):
    """Lazy inner steps change WHERE the decay is applied, never WHAT is
    communicated: per inner step each worker still all-reduces exactly one
    u-vector of partial margins.  Guard against drift — the lazy run's
    meter must equal the eager run's (and the analytic schedule) exactly,
    scalar for scalar, round for round, and the modeled-time history must
    be identical record by record."""
    from benchmarks.common import analytic_outer

    n = data.num_instances
    outers, u, q = 2, 2, 4
    cluster = ClusterModel()
    cfg = SVRGConfig(eta=0.2, inner_steps=n // u, outer_iters=outers,
                     batch_size=u, seed=3)
    part = balanced(data.dim, q)
    eager = run_fdsvrg(data, part, LOSS, REG, cfg, cluster)
    lazy_res = run_fdsvrg(data, part, LOSS, REG, cfg, cluster,
                          lazy_updates=lazy)
    assert lazy_res.meter.total_scalars == eager.meter.total_scalars
    assert lazy_res.meter.total_rounds == eager.meter.total_rounds
    _, c1 = analytic_outer("fdsvrg", _spec_of(data), q, u=u, cluster=cluster)
    assert lazy_res.meter.total_scalars == outers * c1
    for he, hl in zip(eager.history, lazy_res.history):
        assert hl.comm_scalars == he.comm_scalars
        assert hl.modeled_time_s == he.modeled_time_s


def test_worker_simulation_meters_like_the_jitted_driver(data):
    """The message-level executable spec lands on the same closed form."""
    q, outers, m = 4, 2, 10
    cfg = SVRGConfig(eta=0.2, inner_steps=m, outer_iters=outers, seed=3)
    sim = fdsvrg_worker_simulation(data, balanced(data.dim, q), LOSS, REG, cfg)
    _, c1 = COSTS.outer_cost(
        "fdsvrg", n=data.num_instances, d=data.dim, nnz=int(data.nnz_max),
        q=q, u=1, inner_steps=m,
    )
    assert sim.meter.total_scalars == outers * c1


def test_sharded_driver_modeled_time_matches_cost_model(data):
    """run_fdsvrg_sharded charges COSTS too (q=1 mesh: zero scalars, pure
    compute closed form)."""
    from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, run_fdsvrg_sharded

    outers, m, u = 2, 8, 2
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=0.2, inner_steps=m, batch_size=u, lam=1e-3,
    )
    res = run_fdsvrg_sharded(data, mesh, cfg, feature_axes=("model",),
                             outer_iters=outers, seed=0)
    t1, c1 = COSTS.outer_cost(
        "fdsvrg", n=data.num_instances, d=data.dim, nnz=int(data.nnz_max),
        q=1, u=u, inner_steps=m,
    )
    assert c1 == 0 and res.meter.total_scalars == 0
    np.testing.assert_allclose(
        res.history[-1].modeled_time_s, outers * t1, rtol=1e-12
    )


def test_cost_model_basic_shapes():
    """Pin the §4.5 closed forms themselves (scalars side)."""
    _, c = COSTS.outer_cost("fdsvrg", n=100, d=1000, nnz=10, q=8, u=4,
                            inner_steps=25)
    assert c == 2 * 8 * 100 + 25 * 2 * 8 * 4
    _, c = COSTS.outer_cost("dsvrg", n=100, d=1000, nnz=10, q=8)
    assert c == 2 * 8 * 1000 + 2 * 1000
    _, c = COSTS.outer_cost("synsvrg", n=96, d=1000, nnz=10, q=8, u=1)
    assert c == 2 * 8 * 1000 + 12 * 8 * (1000 + 20)
    _, c = COSTS.outer_cost("pslite_sgd", n=96, d=1000, nnz=10, q=8)
    assert c == 96 * (1000 + 20)
    _, c = COSTS.outer_cost("asysvrg", n=96, d=1000, nnz=10, q=8)
    assert c == 2 * 8 * 1000 + 96 * (1000 + 20)
    # q = 1 communicates nothing on the tree path
    _, c = COSTS.outer_cost("fdsvrg", n=100, d=1000, nnz=10, q=1, u=1)
    assert c == 0
    with pytest.raises(ValueError):
        COSTS.outer_cost("nope", n=1, d=1, nnz=1, q=1)


# ---------------------------------------------------------------------------
# 2. harness semantics
# ---------------------------------------------------------------------------


def test_harness_rotates_snapshot_one_extra_full_gradient():
    """snapshot runs outer_iters + 1 times (initial + one per epoch) and
    the epoch at outer t consumes the snapshot taken at the iterate
    entering it."""
    calls = {"snapshot": [], "epoch": []}

    def snapshot(w):
        calls["snapshot"].append(float(w[0]))
        return w * 0.0, jnp.zeros((1,))

    def epoch(t, rng, w, z, s0):
        calls["epoch"].append((t, float(w[0])))
        return w + 1.0

    res = run_outer_loop(
        outer_iters=3, seed=0, init_w=jnp.zeros((2,)),
        snapshot=snapshot, epoch=epoch,
        evaluate=lambda w, z, s0: (float(w[0]), 0.0),
    )
    assert calls["snapshot"] == [0.0, 1.0, 2.0, 3.0]  # outers + 1
    assert calls["epoch"] == [(0, 0.0), (1, 1.0), (2, 2.0)]
    assert [h.objective for h in res.history] == [1.0, 2.0, 3.0]
    assert isinstance(res, RunResult)
    assert res.meter.total_scalars == 0  # backend=None: fresh empty meter


@pytest.mark.parametrize(
    "runner",
    [
        lambda d, cfg: baselines.run_pslite_sgd(d, 4, LOSS, REG, cfg),
        lambda d, cfg: baselines.run_asy_svrg(d, 4, LOSS, REG, cfg),
    ],
    ids=["pslite", "asysvrg"],
)
def test_async_grad_norm_recorded_at_post_epoch_iterate(data, runner):
    """The async pair reports the same-iterate residual like everyone
    else (PS-Lite included — its snapshot is reporting-only)."""
    cfg = SVRGConfig(eta=0.1, inner_steps=16, outer_iters=2, seed=13)
    res = runner(data, cfg)
    gd, _ = full_gradient(data, res.w, LOSS)
    want = optimality_norm(gd, res.w, REG, cfg.eta)
    np.testing.assert_allclose(res.history[-1].grad_norm, want, rtol=1e-4,
                               atol=1e-7)


def test_history_schema_uniform_across_all_drivers(data):
    """Every driver emits the same OuterRecord schema with finite
    objectives — the shard_map driver included (no more bare tuples)."""
    from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, run_fdsvrg_sharded

    cfg = SVRGConfig(eta=0.1, inner_steps=8, outer_iters=2, seed=1)
    mesh = make_mesh((1,), ("model",))
    sh_cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=0.1, inner_steps=8, batch_size=1, lam=1e-3,
    )
    results = [
        run_serial_svrg(data, LOSS, REG, cfg),
        run_fdsvrg(data, balanced(data.dim, 4), LOSS, REG, cfg),
        fdsvrg_worker_simulation(data, balanced(data.dim, 4), LOSS, REG, cfg),
        baselines.run_dsvrg(data, 4, LOSS, REG, cfg),
        baselines.run_syn_svrg(data, 4, LOSS, REG, cfg),
        baselines.run_asy_svrg(data, 4, LOSS, REG, cfg),
        baselines.run_pslite_sgd(data, 4, LOSS, REG, cfg),
        run_fdsvrg_sharded(data, mesh, sh_cfg, feature_axes=("model",),
                           outer_iters=2, seed=1),
    ]
    for res in results:
        assert isinstance(res, RunResult)
        assert len(res.history) == 2
        for h in res.history:
            assert isinstance(h, OuterRecord)
            assert np.isfinite(h.objective)
            assert np.isfinite(h.grad_norm)
            assert h.wall_time_s >= 0.0
        assert res.history[0].wall_time_s <= res.history[-1].wall_time_s


# ---------------------------------------------------------------------------
# 3. satellites
# ---------------------------------------------------------------------------


def test_inner_epoch_compiles_once_across_lambda_sweep(data):
    """lam is traced (like _async_epoch): a 3-lambda sweep reuses ONE
    compiled scan instead of recompiling per point (the
    lambda_sensitivity regression)."""
    cfg = SVRGConfig(eta=0.2, inner_steps=4, outer_iters=1)
    before = _inner_epoch._cache_size()
    for lam in (1e-3, 2e-3, 5e-3):
        run_fdsvrg(data, balanced(data.dim, 4), LOSS, losses.l2(lam), cfg)
    assert _inner_epoch._cache_size() - before <= 1
    # and the traced path matches a fresh static-value run numerically
    a = run_fdsvrg(data, balanced(data.dim, 4), LOSS, losses.l2(2e-3), cfg)
    b = run_serial_svrg(data, LOSS, losses.l2(2e-3), cfg)
    np.testing.assert_allclose(np.asarray(a.w), np.asarray(b.w),
                               rtol=2e-4, atol=2e-6)


def test_inner_epoch_kernels_require_static_lams(data):
    """The fused kernels bake lambda in at compile time — calling the
    kernel path without the static triple fails loudly, not silently."""
    from repro.data.block_csr import BlockCSR

    block = BlockCSR.from_padded(data, balanced(data.dim, 1))
    with pytest.raises(ValueError, match="kernel_lams"):
        _inner_epoch(
            block.indices, block.values, data.labels,
            jnp.zeros((data.dim,)), jnp.zeros((data.dim,)),
            jnp.zeros((data.num_instances,)),
            jnp.zeros((2, 1), jnp.int32), 0.1, jnp.ones(2, jnp.float32),
            "logistic", "l2", 1e-3, block.block_dims, True,
        )


@pytest.mark.parametrize("method", ["serial", "fdsvrg"])
def test_run_method_plumbs_use_kernels(data, method):
    """BENCH_* trajectories can exercise the Pallas hot path: run_method's
    use_kernels flag reaches the drivers and stays bit-identical."""
    import benchmarks.common as common

    ref = common.run_method(method, data, 4, 1e-3, outer_iters=2)
    ker = common.run_method(method, data, 4, 1e-3, outer_iters=2,
                            use_kernels=True)
    np.testing.assert_array_equal(np.asarray(ref.w), np.asarray(ker.w))
    assert ref.meter.total_scalars == ker.meter.total_scalars


# ---------------------------------------------------------------------------
# 4. honest accounting under faults: the drift guard, faulted
# ---------------------------------------------------------------------------


def test_faulty_meter_is_analytic_schedule_plus_exact_retries(data):
    """Under a drop-fault plan the meter stays falsifiable: the delivered
    traffic equals the fault-free analytic schedule EXACTLY (same closed
    form as the clean drift guard), and the total exceeds it by exactly
    the retransmitted bytes recorded under the ``"retry"`` kind — which
    an independent replay of the same seeded plan over the driver's
    metering call sequence reproduces scalar-for-scalar."""
    from benchmarks.common import analytic_outer
    from repro.dist import FaultPlan, FaultyBackend, RetryPolicy, SimBackend

    q, u, outers = 4, 2, 2
    n = data.num_instances
    cluster = ClusterModel()
    cfg = SVRGConfig(eta=0.2, inner_steps=n // u, outer_iters=outers,
                     batch_size=u)
    plan = FaultPlan(seed=5, drop_prob=0.2)
    retry = RetryPolicy(max_retries=8)
    backend = FaultyBackend(SimBackend(q, cluster), plan, retry)
    res = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg,
                     backend=backend)

    _, c1 = analytic_outer("fdsvrg", _spec_of(data), q, u=u, cluster=cluster)
    m = res.meter
    # delivered collectives: the fault-free schedule, untouched
    assert m.by_kind["tree_reduce"] == outers * c1
    # retransmissions: present, and the only thing added to the total
    retries = m.by_kind["retry"]
    assert retries > 0
    assert m.total_scalars == outers * c1 + retries

    # independent replay: same plan + policy over the jitted driver's
    # metering sequence (per outer: one N-payload tree, then M u-trees)
    replay = FaultyBackend(SimBackend(q, cluster), plan, retry)
    for _ in range(outers):
        replay.meter_tree(payload=n)
        replay.meter_tree(payload=u, steps=cfg.inner_steps)
    assert replay.meter.by_kind["retry"] == retries

    # drops retransmit deterministic partials: the trajectory cannot move
    clean = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg, cluster)
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(clean.w))
    assert [h.objective for h in res.history] == \
        [h.objective for h in clean.history]
