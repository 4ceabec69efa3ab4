"""Equivalence tests for the repro.dist substrate.

The load-bearing property: the three Collectives backends are
interchangeable — same iterates (bit-for-bit, thanks to the shared
canonical tree-order summation), same metered traffic (the §4.5 closed
forms live in ONE place) — so any method ported onto the substrate can be
compared across backends and against any other method on the same meter.

The ``faulty-*`` kinds run the SAME equivalence suite through a
:class:`repro.dist.FaultyBackend` wrapping each backend with a no-fault
:class:`repro.dist.FaultPlan`: with no faults armed the wrapper must be
a true no-op — bit-identical iterates and scalar-identical meters.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses
from repro.core.fdsvrg import SVRGConfig, fdsvrg_worker_simulation, run_fdsvrg
from repro.core.partition import balanced
from repro.dist import (
    ClusterModel,
    Collectives,
    CommReport,
    FaultPlan,
    FaultyBackend,
    LocalBackend,
    ShardMapBackend,
    SimBackend,
    tree_rounds,
)
from repro.data.synthetic import make_sparse_classification

LOSS = losses.logistic
REG = losses.l2(1e-3)
Q = 4


def make_backend(kind: str, q: int = Q) -> Collectives:
    if kind.startswith("faulty-"):
        # the wrapper with nothing armed: must behave as its inner backend
        return FaultyBackend(make_backend(kind[len("faulty-"):], q),
                             FaultPlan())
    if kind == "local":
        return LocalBackend(q)
    if kind == "sim":
        return SimBackend(q)
    if kind == "shardmap-interpret":
        return ShardMapBackend(q=q, interpret=True)
    raise ValueError(kind)


BACKENDS = ["local", "sim", "shardmap-interpret"]
BACKENDS += [f"faulty-{k}" for k in BACKENDS]


@pytest.fixture(scope="module")
def data():
    return make_sparse_classification(
        dim=256, num_instances=48, nnz_per_instance=8, seed=2
    )


# ---------------------------------------------------------------------------
# Primitive-level equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", BACKENDS)
def test_all_reduce_value_and_meter(kind):
    rng = np.random.default_rng(0)
    parts = [jnp.asarray(rng.normal(size=(5,)).astype(np.float32)) for _ in range(Q)]
    b = make_backend(kind)
    got = b.all_reduce(parts, payload=5)
    np.testing.assert_allclose(
        np.asarray(got), np.sum([np.asarray(p) for p in parts], axis=0),
        rtol=1e-5, atol=1e-6,
    )
    # paper §4.5: one tree reduce+broadcast of 5 scalars among Q workers
    assert b.meter.total_scalars == 2 * Q * 5
    assert b.meter.total_rounds == tree_rounds(Q)
    assert b.meter.by_kind == {"tree_reduce": 2 * Q * 5}


def test_backends_all_reduce_bitwise_identical():
    """All backends sum in the canonical Figure-5 order, so the floats
    match exactly, not just approximately."""
    rng = np.random.default_rng(1)
    parts = [jnp.asarray(rng.normal(size=(7,)).astype(np.float32)) for _ in range(Q)]
    results = [np.asarray(make_backend(k).all_reduce(parts)) for k in BACKENDS]
    for r in results[1:]:
        np.testing.assert_array_equal(results[0], r)


def test_protocol_conformance():
    for kind in BACKENDS:
        b = make_backend(kind)
        assert isinstance(b, Collectives)
        assert b.q == Q
        b.meter_tree(payload=3, steps=2)
        b.p2p(10, "push")
        b.charge(flops=1e6, scalars=100, rounds=2)
        b.charge_seconds(0.5)
        assert b.modeled_time_s > 0.5
        rep = b.report("m")
        assert rep.scalars == b.meter.total_scalars
        assert rep.bytes_on_wire == rep.scalars * b.cluster.bytes_per_scalar


@pytest.mark.parametrize("kind", BACKENDS)
def test_all_reduce_rejects_wrong_partial_count(kind):
    b = make_backend(kind)
    with pytest.raises(ValueError, match="one partial per worker"):
        b.all_reduce([jnp.ones(2)] * (Q - 1))


def test_q1_backends_meter_nothing():
    for kind in BACKENDS:
        b = make_backend(kind, q=1)
        out = b.all_reduce([jnp.ones(3)])
        np.testing.assert_array_equal(np.asarray(out), np.ones(3))
        assert b.meter.total_scalars == 0


def test_shardmap_backend_guards():
    b = ShardMapBackend(q=2, interpret=True)
    with pytest.raises(ValueError):
        b.device_all_reduce(jnp.ones(()))
    with pytest.raises(ValueError):
        ShardMapBackend(q=2, tree_mode="ring")
    with pytest.raises(ValueError):
        ShardMapBackend()  # neither mesh nor q
    # a real-mesh backend refuses the host path
    from repro.dist.compat import make_mesh

    real = ShardMapBackend(mesh=make_mesh((1,), ("model",)))
    with pytest.raises(ValueError):
        real.all_reduce([jnp.ones(2)])
    # a backend built on one mesh/axes cannot drive an iteration on another
    # (note jax interns equal meshes, so differ by axis name)
    from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, make_fullgrad

    other = make_mesh((1,), ("data",))
    cfg = FDSVRGShardedConfig(dim=8, num_instances=4, nnz_max=2,
                              eta=0.1, inner_steps=2)
    with pytest.raises(ValueError, match="different mesh"):
        make_fullgrad(other, cfg, feature_axes=("data",), backend=real)


# ---------------------------------------------------------------------------
# FD-SVRG-level equivalence (the satellite acceptance test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", BACKENDS)
def test_fdsvrg_run_identical_across_backends(data, kind):
    """SimBackend, LocalBackend, and interpret-mode ShardMapBackend drive
    the worker simulation to identical iterates AND identical metered
    byte counts for a small FD-SVRG run."""
    cfg = SVRGConfig(eta=0.2, inner_steps=10, outer_iters=2, seed=13)
    part = balanced(data.dim, Q)
    ref = fdsvrg_worker_simulation(
        data, part, LOSS, REG, cfg, backend=SimBackend(Q)
    )
    res = fdsvrg_worker_simulation(
        data, part, LOSS, REG, cfg, backend=make_backend(kind)
    )
    w, meter, ref_meter = res.w, res.meter, ref.meter
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ref.w))
    assert meter.total_scalars == ref_meter.total_scalars
    assert meter.total_rounds == ref_meter.total_rounds
    assert dict(meter.by_kind) == dict(ref_meter.by_kind)
    # and the closed form itself: per outer, one N-payload tree + M u-trees
    n, m, u = data.num_instances, cfg.inner_steps, cfg.batch_size
    assert meter.total_scalars == cfg.outer_iters * (2 * Q * n + 2 * Q * u * m)


def test_run_fdsvrg_accepts_backend(data):
    """The jitted driver meters through an injected backend and the
    result's meter IS the backend's meter."""
    cfg = SVRGConfig(eta=0.2, inner_steps=8, outer_iters=2, seed=3)
    backend = SimBackend(Q, ClusterModel(flops_per_s=1e8))
    res = run_fdsvrg(data, balanced(data.dim, Q), LOSS, REG, cfg, backend=backend)
    assert res.meter is backend.meter
    assert res.history[-1].comm_scalars == backend.meter.total_scalars
    assert res.history[-1].modeled_time_s == pytest.approx(backend.modeled_time_s)
    # jitted driver and worker simulation agree on the accounting
    sim = fdsvrg_worker_simulation(
        data, balanced(data.dim, Q), LOSS, REG, cfg, backend=SimBackend(Q)
    )
    assert backend.meter.total_scalars == sim.meter.total_scalars


def test_run_fdsvrg_rejects_mismatched_backend_q(data):
    cfg = SVRGConfig(eta=0.2, inner_steps=4, outer_iters=1)
    with pytest.raises(ValueError, match="q=8 workers but the partition"):
        run_fdsvrg(data, balanced(data.dim, Q), LOSS, REG, cfg,
                   backend=SimBackend(8))


def test_baselines_share_the_substrate(data):
    """Two baselines run through injected Collectives backends and report
    through the same meter machinery as FD-SVRG."""
    from repro.core import baselines

    cfg = SVRGConfig(eta=0.1, inner_steps=12, outer_iters=2)
    b_ds = LocalBackend(Q)
    ds = baselines.run_dsvrg(data, Q, LOSS, REG, cfg, backend=b_ds)
    assert ds.meter is b_ds.meter
    assert ds.meter.total_scalars == cfg.outer_iters * (2 * Q * data.dim + 2 * data.dim)

    b_ps = LocalBackend(Q)
    ps = baselines.run_syn_svrg(data, Q, LOSS, REG, cfg, backend=b_ps)
    assert ps.meter is b_ps.meter
    assert set(ps.meter.by_kind) == {"ps_fullgrad", "ps_inner"}

    # apples-to-apples reports from the shared report type
    rep_ds = CommReport.from_result("dsvrg", Q, ds)
    rep_ps = CommReport.from_result("synsvrg", Q, ps)
    assert rep_ps.bytes_on_wire > rep_ds.bytes_on_wire > 0


def test_meter_tree_aggregate_matches_loop():
    """meter_tree(payload, steps) must equal `steps` separate trees in
    scalars and rounds (the jitted path's aggregate accounting)."""
    agg, loop = SimBackend(8), SimBackend(8)
    agg.meter_tree(payload=3, steps=5)
    for _ in range(5):
        loop.meter_tree(payload=3)
    assert agg.meter.total_scalars == loop.meter.total_scalars
    assert agg.meter.total_rounds == loop.meter.total_rounds
