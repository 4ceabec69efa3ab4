"""Tests for the deployable shard_map FD-SVRG (core/fdsvrg_shardmap.py).

Single-device mesh in-process; an 8-device feature-sharded run executes in
a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
main test process must keep seeing exactly 1 device).

The shard_map path consumes the block-local layout placed on the mesh
(BlockCSR.on_mesh): [q*N, B] re-indexed rows and their row groups split
over the feature axes, so workers never see global ids.  Its one program
pair, make_fullgrad + make_inner_epoch, is driven here outer by outer.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses
from repro.core.fdsvrg import SVRGConfig, run_fdsvrg, run_serial_svrg
from repro.core.fdsvrg_shardmap import (
    FDSVRGShardedConfig,
    make_fullgrad,
    make_inner_epoch,
    run_fdsvrg_sharded,
)
from repro.core.partition import balanced
from repro.data.block_csr import BlockCSR
from repro.data.synthetic import make_sparse_classification
from repro.dist import SimBackend, make_mesh


def _mesh_outer(data, mesh, cfg, q=1):
    """One outer iteration of the mesh's program pair on the placed
    layout: ``(w, samples) -> (w_next, z, s0)``."""
    placed = BlockCSR.from_padded(data, balanced(data.dim, q)).on_mesh(
        mesh, ("model",)
    )
    fullgrad = make_fullgrad(mesh, cfg, feature_axes=("model",))
    inner_epoch = make_inner_epoch(mesh, cfg, feature_axes=("model",))

    def outer(w, samples):
        z, s0 = fullgrad(w, placed.groups, placed.labels)
        w = inner_epoch(w, z, s0, placed.indices, placed.values,
                        placed.labels, jnp.asarray(samples))
        return w, z, s0

    return outer


def test_shardmap_single_device_matches_serial():
    data = make_sparse_classification(
        dim=512, num_instances=64, nnz_per_instance=8, seed=0
    )
    eta, inner, outers, u, lam = 0.2, 16, 3, 2, 1e-3
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=eta, inner_steps=inner, batch_size=u, lam=lam,
    )
    outer = _mesh_outer(data, mesh, cfg)

    rng = np.random.default_rng(7)
    w = jnp.zeros((data.dim,), jnp.float32)
    for t in range(outers):
        samples = rng.integers(0, data.num_instances, size=(inner, u)).astype(np.int32)
        w, z, s0 = outer(w, samples)
    assert np.all(np.isfinite(np.asarray(w)))

    # same sample stream through the serial reference
    rng = np.random.default_rng(7)
    cfg_ref = SVRGConfig(eta=eta, inner_steps=inner, outer_iters=outers,
                         batch_size=u, seed=0)
    from repro.core.fdsvrg import _full_grad_blocks, _inner_epoch

    block = BlockCSR.from_padded(data, balanced(data.dim, 1))
    w_ref = jnp.zeros((data.dim,), jnp.float32)
    for t in range(outers):
        z_ref, s0_ref = _full_grad_blocks(
            block.groups, data.labels, w_ref,
            "logistic", block.block_dims, False,
        )
        samples = rng.integers(0, data.num_instances, size=(inner, u)).astype(np.int32)
        w_ref = _inner_epoch(
            block.indices, block.values, data.labels, w_ref, z_ref, s0_ref,
            jnp.asarray(samples), eta, jnp.ones(inner, jnp.float32),
            "logistic", "l2", lam, block.block_dims, False,
        )
    # the last outer's snapshot, then its iterate
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s0_ref), rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=2e-4, atol=1e-6)


def test_shardmap_butterfly_mode_single_device():
    data = make_sparse_classification(
        dim=256, num_instances=32, nnz_per_instance=8, seed=1
    )
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=0.1, inner_steps=8, batch_size=1, tree_mode="butterfly",
    )
    samples = np.zeros((8, 1), dtype=np.int32)
    w, _, s0 = _mesh_outer(data, mesh, cfg)(
        jnp.zeros((data.dim,), jnp.float32), samples
    )
    assert np.all(np.isfinite(np.asarray(w)))
    assert np.all(np.isfinite(np.asarray(s0)))


def test_shardmap_use_kernels_bit_identical_single_device():
    """The fused-kernel worker (interpret mode) must produce bit-identical
    iterates to the jnp reference worker — same mesh, same samples."""
    data = make_sparse_classification(
        dim=384, num_instances=48, nnz_per_instance=8, seed=3
    )
    mesh = make_mesh((1,), ("model",))
    samples = np.random.default_rng(5).integers(
        0, data.num_instances, size=(12, 2)
    ).astype(np.int32)
    results = {}
    for use_kernels in (False, True):
        cfg = FDSVRGShardedConfig(
            dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
            eta=0.2, inner_steps=12, batch_size=2, lam=1e-3,
            use_kernels=use_kernels,
        )
        outer = _mesh_outer(data, mesh, cfg)
        w = jnp.zeros((data.dim,), jnp.float32)
        for _ in range(2):
            w, _, _ = outer(w, samples)
        results[use_kernels] = np.asarray(w)
    np.testing.assert_array_equal(results[True], results[False])


def test_sharded_driver_metering_matches_simulation_driver():
    """run_fdsvrg_sharded must charge the same §4.5 closed forms —
    compute terms included — as run_fdsvrg (both consume repro.dist.COSTS
    now), so the two drivers' meters and modeled times are bit-consistent
    for identical shapes, record by record."""
    from repro.dist import ShardMapBackend

    data = make_sparse_classification(
        dim=512, num_instances=64, nnz_per_instance=8, seed=0
    )
    inner, u, outers = 8, 4, 2
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=0.1, inner_steps=inner, batch_size=u, lam=1e-3,
    )
    backend = ShardMapBackend(mesh=mesh, feature_axes=("model",))
    res = run_fdsvrg_sharded(
        data, mesh, cfg, feature_axes=("model",), outer_iters=outers, seed=0,
        backend=backend,
    )
    assert res.meter is backend.meter
    assert backend.modeled_time_s > 0.0

    sim_backend = SimBackend(backend.q)
    sim_cfg = SVRGConfig(eta=0.1, inner_steps=inner, outer_iters=outers,
                         batch_size=u, seed=0)
    sim = run_fdsvrg(data, balanced(data.dim, backend.q), losses.logistic,
                     losses.l2(1e-3), sim_cfg, backend=sim_backend)
    assert backend.meter.total_scalars == sim_backend.meter.total_scalars
    np.testing.assert_allclose(
        backend.modeled_time_s, sim_backend.modeled_time_s, rtol=1e-12
    )
    # the two drivers run the same harness: record-by-record schema parity
    for h_sh, h_sim in zip(res.history, sim.history):
        assert h_sh.outer == h_sim.outer
        assert h_sh.comm_scalars == h_sim.comm_scalars
        assert h_sh.comm_rounds == h_sim.comm_rounds
        np.testing.assert_allclose(h_sh.modeled_time_s, h_sim.modeled_time_s,
                                   rtol=1e-12)


def test_sharded_driver_matches_sim_driver_iterates_and_objective():
    """Same seed => same sample stream through the shared harness: the
    q=1 shard_map driver and run_fdsvrg produce matching iterates and
    per-outer objectives (the sharded path finally reports a real
    RunResult with objectives, like everyone else)."""
    data = make_sparse_classification(
        dim=384, num_instances=48, nnz_per_instance=8, seed=1
    )
    inner, u, outers = 10, 2, 2
    mesh = make_mesh((1,), ("model",))
    cfg = FDSVRGShardedConfig(
        dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=0.2, inner_steps=inner, batch_size=u, lam=1e-3,
    )
    res = run_fdsvrg_sharded(
        data, mesh, cfg, feature_axes=("model",), outer_iters=outers, seed=7
    )
    sim_cfg = SVRGConfig(eta=0.2, inner_steps=inner, outer_iters=outers,
                         batch_size=u, seed=7)
    sim = run_fdsvrg(data, balanced(data.dim, 1), losses.logistic,
                     losses.l2(1e-3), sim_cfg)
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(sim.w), rtol=2e-4, atol=2e-6
    )
    for h_sh, h_sim in zip(res.history, sim.history):
        np.testing.assert_allclose(h_sh.objective, h_sim.objective, rtol=1e-5)
        np.testing.assert_allclose(h_sh.grad_norm, h_sim.grad_norm, rtol=1e-3,
                                   atol=1e-6)


def test_sharded_driver_gnorm_is_post_epoch_residual():
    """Every record's grad_norm must be the optimality residual at that
    outer's post-epoch iterate, not at the snapshot the epoch started
    from (one epoch stale for reporting purposes)."""
    from repro.core.fdsvrg import full_gradient, optimality_norm

    data = make_sparse_classification(
        dim=256, num_instances=32, nnz_per_instance=8, seed=2
    )
    # A plain jax.make_mesh mesh on purpose: its axes are Explicit, and the
    # host-side full_gradient below must still index the returned w.
    mesh = jax.make_mesh((1,), ("model",))
    for reg_name, lam, lam2 in (("l2", 1e-3, 0.0), ("l1", 2e-3, 0.0)):
        cfg = FDSVRGShardedConfig(
            dim=data.dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
            eta=0.2, inner_steps=8, batch_size=2,
            reg_name=reg_name, lam=lam, lam2=lam2,
        )
        res = run_fdsvrg_sharded(
            data, mesh, cfg, feature_axes=("model",), outer_iters=2, seed=0
        )
        gd, _ = full_gradient(data, res.w, losses.logistic)
        want = optimality_norm(
            gd, res.w, losses.Regularizer(reg_name, lam, lam2), cfg.eta
        )
        np.testing.assert_allclose(res.history[-1].grad_norm, want, rtol=1e-4)


def test_sharded_driver_preserves_float64():
    """Satellite regression: the sharded driver used to hardcode
    jnp.float32 for the initial iterate, silently demoting float64 runs —
    it must initialize from the data's dtype (same bug class PR 3 fixed
    in _run_async)."""
    from repro.data.sparse import PaddedCSR

    data32 = make_sparse_classification(
        dim=128, num_instances=16, nnz_per_instance=4, seed=0
    )
    with jax.enable_x64(True):
        data = PaddedCSR(
            indices=jnp.asarray(np.asarray(data32.indices)),
            values=jnp.asarray(np.asarray(data32.values), dtype=jnp.float64),
            labels=jnp.asarray(np.asarray(data32.labels), dtype=jnp.float64),
            dim=data32.dim,
        )
        mesh = make_mesh((1,), ("model",))
        cfg = FDSVRGShardedConfig(
            dim=data.dim, num_instances=data.num_instances,
            nnz_max=data.nnz_max, eta=0.2, inner_steps=4, batch_size=2,
            lam=1e-3,
        )
        res = run_fdsvrg_sharded(
            data, mesh, cfg, feature_axes=("model",), outer_iters=1, seed=0
        )
        assert res.w.dtype == jnp.float64
        assert np.all(np.isfinite(np.asarray(res.w)))
        assert np.isfinite(res.history[-1].objective)


_SUBPROCESS_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import losses
    from repro.core.fdsvrg import SVRGConfig, run_serial_svrg
    from repro.core.fdsvrg_shardmap import (
        FDSVRGShardedConfig, make_fullgrad, make_inner_epoch,
    )
    from repro.core.partition import balanced
    from repro.data.block_csr import BlockCSR
    from repro.data.synthetic import make_sparse_classification
    from repro.dist import make_mesh

    assert jax.device_count() == 8
    data = make_sparse_classification(dim=512, num_instances=48, nnz_per_instance=8, seed=0)
    eta, inner, outers, u, lam = 0.2, 12, 2, 2, 1e-3
    mesh = make_mesh((8,), ("model",))
    cfg = FDSVRGShardedConfig(dim=data.dim, num_instances=data.num_instances,
                              nnz_max=data.nnz_max, eta=eta, inner_steps=inner,
                              batch_size=u, lam=lam, tree_mode="{mode}")
    fullgrad = make_fullgrad(mesh, cfg, feature_axes=("model",))
    inner_epoch = make_inner_epoch(mesh, cfg, feature_axes=("model",))
    placed = BlockCSR.from_padded(data, balanced(data.dim, 8)).on_mesh(mesh, ("model",))
    rng = np.random.default_rng(3)
    w = jnp.zeros((data.dim,), jnp.float32)
    all_samples = []
    for t in range(outers):
        s = rng.integers(0, data.num_instances, size=(inner, u)).astype(np.int32)
        all_samples.append(s)
        z, s0 = fullgrad(w, placed.groups, placed.labels)
        w = inner_epoch(w, z, s0, placed.indices, placed.values, placed.labels,
                        jnp.asarray(s))

    # serial reference with the same sample stream
    from repro.core.fdsvrg import _full_grad_blocks, _inner_epoch
    block = BlockCSR.from_padded(data, balanced(data.dim, 1))
    w_ref = jnp.zeros((data.dim,), jnp.float32)
    for t in range(outers):
        z, s0 = _full_grad_blocks(block.groups, data.labels, w_ref,
                                  "logistic", block.block_dims, False)
        w_ref = _inner_epoch(block.indices, block.values, data.labels, w_ref, z, s0,
                             jnp.asarray(all_samples[t]), eta,
                             jnp.ones(inner, jnp.float32),
                             "logistic", "l2", lam, block.block_dims, False)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=3e-4, atol=3e-6)
    print("OK-8DEV")
    """
)


@pytest.mark.parametrize("mode", ["psum", "butterfly"])
def test_shardmap_eight_devices_subprocess(mode):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG.replace("{mode}", mode)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK-8DEV" in proc.stdout


_FOUR_DEVICE_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.api import ExperimentSpec, solve
    from repro.core import losses
    from repro.data.synthetic import make_sparse_classification
    from repro.dist import make_mesh

    assert jax.device_count() == 4
    # d = 513 is odd, as every Table-1 width is: the sharded driver pads it
    # to 516 zero-extended features and slices the pad back off.
    data = make_sparse_classification(dim=513, num_instances=40, nnz_per_instance=8, seed=1)
    common = dict(data=data, eta=0.2, inner_steps=10, outer_iters=2, batch_size=2,
                  reg=losses.l2(1e-3), seed=4)
    fd = solve(ExperimentSpec(method="fdsvrg", q=4, **common))
    for mesh in (make_mesh((4,), ("model",)), None):  # None: solve's default
        sh = solve(ExperimentSpec(method="fdsvrg_sharded", mesh=mesh, **common))
        assert sh.w.shape == (513,), sh.w.shape
        assert len(sh.w.sharding.device_set) == 4, sh.w.sharding
        np.testing.assert_allclose(np.asarray(sh.w), np.asarray(fd.w), rtol=2e-4, atol=2e-6)
        for a, b in zip(sh.history, fd.history):
            np.testing.assert_allclose(a.objective, b.objective, rtol=1e-5)
            assert a.comm_scalars == b.comm_scalars
    print("OK-4DEV")
    """
)


def test_sharded_driver_pads_odd_dim_on_four_devices_subprocess():
    """On 4 devices an odd d is padded to a multiple of q and the result
    matches fdsvrg at q=4 on the same sample stream — both on an explicit
    4-device mesh and on the mesh solve() builds when given none, which
    must span all 4 local devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_PROG],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK-4DEV" in proc.stdout


_CARRY_PROG = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import obs
    from repro.api import ExperimentSpec, solve
    from repro.core import losses
    from repro.data.synthetic import make_sparse_classification
    from repro.dist import make_mesh

    mesh = make_mesh((4,), ("model",))
    dim = {dim}
    padded = -(-dim // 4) * 4
    data = make_sparse_classification(dim=dim, num_instances=40, nnz_per_instance=8, seed=1)
    common = dict(method="fdsvrg_sharded", data=data, mesh=mesh, eta=0.2, inner_steps=10,
                  outer_iters=2, batch_size=2, reg=losses.l2(1e-3))

    def counted(fn):
        obs.reset()
        with jax.profiler.trace(tempfile.mkdtemp()):
            out = fn()
        counters = obs.totals()["counters"]
        obs.reset()
        return out, counters

    first = solve(ExperimentSpec(seed=4, **common))
    assert first.final.w.shape == first.final.z.shape == (padded,)
    assert first.final.s0.shape == (40,)
    assert first.final.w.sharding == NamedSharding(mesh, P(("model",)))
    np.testing.assert_array_equal(np.asarray(first.final.w)[:dim], np.asarray(first.w))
    w = jax.block_until_ready(first.w)
    copy = jnp.asarray(np.asarray(w))
    warm, carried = counted(lambda: solve(ExperimentSpec(seed=5, init_w=w, **common)))
    cold, computed = counted(lambda: solve(ExperimentSpec(seed=5, init_w=copy, **common)))
    assert carried["snapshot0.carried"] == 1 and "snapshot0.computed" not in carried
    assert computed["snapshot0.computed"] == 1 and "snapshot0.carried" not in computed
    assert 3 * carried["full_grad.lanes"] == 2 * computed["full_grad.lanes"]
    np.testing.assert_array_equal(np.asarray(warm.w), np.asarray(cold.w))
    assert [h.objective for h in warm.history] == [h.objective for h in cold.history]
    assert [h.grad_norm for h in warm.history] == [h.grad_norm for h in cold.history]
    print("OK-CARRY")
    """
)


@pytest.mark.parametrize("dim", [513, 516])
def test_sharded_warm_call_carries_the_padded_snapshot_subprocess(dim):
    """On 4 devices, with d that q divides and d that it does not: the
    result's final snapshot is in the mesh layout (padded, w and z
    feature-sharded, s0 replicated), a call from the returned iterate
    starts from it and computes one full gradient fewer, and it runs bit
    for bit as the same call from a copy that misses the slot."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CARRY_PROG.replace("{dim}", str(dim))],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK-CARRY" in proc.stdout
