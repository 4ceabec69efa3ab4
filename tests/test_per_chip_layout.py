"""The layout of feature blocks that are made one per device
(``BlockCSR.from_blocks``), its placement on a mesh (``on_mesh``) and
the mesh driver over it: ``fdsvrg_sharded``'s full gradient over row
groups and ``solve()`` on per-device data.

Each test runs in a subprocess on 4 forced host devices (the test
process itself must keep seeing one device), as
``tests/test_fdsvrg_shardmap.py`` does.
"""

import os
import subprocess
import sys
import textwrap

_COMMON = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.fdsvrg_shardmap import mesh_partition
    from repro.data.block_csr import BlockCSR, RowGroups
    from repro.data.sparse import PaddedCSR
    from repro.dist import make_mesh

    assert jax.device_count() == 4
    MESH = make_mesh((4,), ("model",))
    DEVICES = list(NamedSharding(MESH, P("model", None))
                   .devices_indices_map((4, 1)).keys())

    def ragged(n=96, dim=2051, seed=0, lengths=None):
        # Unit-norm rows of heavy-tailed length (ids unique per row), a
        # few stored values set to an explicit 0.0.
        rng = np.random.default_rng(seed)
        if lengths is None:
            lengths = np.clip(np.round(150 * np.exp(0.7 * rng.standard_normal(n))),
                              1, 900).astype(int)
        width = int(max(lengths))
        idx = np.zeros((n, width), np.int32)
        val = np.zeros((n, width), np.float32)
        for i, k in enumerate(lengths):
            idx[i, :k] = np.sort(rng.choice(dim, size=k, replace=False))
            v = rng.gamma(2.0, 1.0, size=k).astype(np.float32)
            val[i, :k] = v / np.linalg.norm(v)
        val[(rng.random(val.shape) < 0.02) & (val != 0)] = 0.0
        labels = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
        return PaddedCSR(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels), dim)

    def per_device(data, lane_multiple=1):
        # The blocks of data, each put on the device that holds its
        # shard of the mesh, at one width, then taken as they sit.
        host = BlockCSR.from_padded(data, mesh_partition(data.dim, 4),
                                    lane_multiple=lane_multiple)
        width = max(host.nnz_budgets)
        pad = lambda a: jnp.pad(a, ((0, 0), (0, width - a.shape[1])))
        idx = [jax.device_put(pad(a), d) for a, d in zip(host.indices, DEVICES)]
        val = [jax.device_put(pad(a), d) for a, d in zip(host.values, DEVICES)]
        return host, BlockCSR.from_blocks(idx, val, host.partition,
                                          data.labels, data.dim)
    """
)


def _run(body: str, marker: str = "OK") -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _COMMON + textwrap.dedent(body)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert marker in proc.stdout, proc.stdout[-2000:]
    return proc.stdout


def test_from_blocks_equals_from_padded_and_stays_on_its_devices():
    """Blocks, stored count, row groups and stacked() of the per-device
    layout equal those of from_padded on the same heavy-tailed rows
    (odd d, so the partition is the padded one); every group of block
    l sits on block l's device, and on_mesh takes the slabs as they sit,
    without a copy."""
    _run(
        """
        data = ragged()
        host, bd = per_device(data)
        assert host.partition.dim == 2052 and bd.dim == host.dim == 2051
        assert bd.stored == host.stored
        assert bd.nnz_max == int((np.asarray(data.values) != 0).sum(1).max())
        for l in range(4):
            width = host.nnz_budgets[l]
            np.testing.assert_array_equal(np.asarray(bd.indices[l])[:, :width],
                                          np.asarray(host.indices[l]))
            np.testing.assert_array_equal(np.asarray(bd.values[l])[:, :width],
                                          np.asarray(host.values[l]))
            g, h = bd.groups[l], host.groups[l]
            assert len(g.indices) == len(h.indices) > 1
            for a, b in zip(g.indices + g.values + g.rows + (g.order,),
                            h.indices + h.values + h.rows + (h.order,)):
                assert a.devices() == {DEVICES[l]}, (l, a.devices())
                np.testing.assert_array_equal(np.asarray(a)[:, :b.shape[1]]
                                              if a.ndim == 2 else np.asarray(a),
                                              np.asarray(b))
        # One row order for every block: a row's class is its longest share.
        for g in bd.groups[1:]:
            np.testing.assert_array_equal(np.asarray(g.order),
                                          np.asarray(bd.groups[0].order))
        rows = NamedSharding(MESH, P("model", None))
        for a, b in zip(bd.stacked(sharding=rows), host.stacked(max(bd.nnz_budgets))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        placed = bd.on_mesh(MESH, ("model",))
        assert placed is bd.on_mesh(MESH, ("model",))
        ptr = lambda arr: sorted(s.data.unsafe_buffer_pointer()
                                 for s in arr.addressable_shards)
        assert ptr(placed.indices) == sorted(a.unsafe_buffer_pointer()
                                             for a in bd.indices)
        assert ptr(placed.values) == sorted(a.unsafe_buffer_pointer()
                                            for a in bd.values)
        np.testing.assert_array_equal(np.asarray(placed.indices),
                                      np.asarray(host.stacked(max(bd.nnz_budgets))[0]))
        print("OK")
        """
    )


def test_mesh_full_grad_over_groups_matches_padded_and_is_exact_on_uniform_rows():
    """make_fullgrad over the mesh-placed row groups against the same
    program over the padded slab (the slab as its one group, which is
    how the full gradient ran before the groups): on ragged rows (z, s0)
    agree to float32 summation order (the groups add a row's entries
    over fewer lanes and scatter rows in class order: a few ulps of the
    largest |z_j|, |s0_i| <= 1); where every row falls in one class as
    wide as the slab, bit for bit."""
    _run(
        """
        from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, make_fullgrad

        def both(data, lane_multiple):
            _, bd = per_device(data, lane_multiple)
            placed = bd.on_mesh(MESH, ("model",))
            repl = NamedSharding(MESH, P())
            ident = jax.device_put(jnp.arange(data.num_instances, dtype=jnp.int32), repl)
            slab = RowGroups((placed.indices,), (placed.values,), (ident,), ident)
            cfg = FDSVRGShardedConfig(dim=bd.partition.dim,
                                      num_instances=data.num_instances,
                                      nnz_max=bd.nnz_max, eta=0.5, inner_steps=1)
            fg = make_fullgrad(MESH, cfg, ("model",))
            w = jnp.asarray(np.random.default_rng(3).normal(
                size=bd.partition.dim).astype(np.float32))
            return (fg(w, placed.groups, placed.labels),
                    fg(w, slab, placed.labels), placed)

        (z, s0), (zp, s0p), placed = both(ragged(), 1)
        assert len(placed.groups.indices) > 1
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s0p), rtol=0, atol=2e-6)
        scale = float(np.abs(np.asarray(zp)).max())
        np.testing.assert_allclose(np.asarray(z), np.asarray(zp), rtol=0,
                                   atol=1e-6 * scale)

        # Every block share of every row at most 128 ids, every slab 128
        # lanes wide: one class, and it is the slab.
        uniform = ragged(n=64, dim=4099, lengths=np.full(64, 200))
        (z, s0), (zp, s0p), placed = both(uniform, 128)
        assert len(placed.groups.indices) == 1
        assert placed.groups.indices[0].shape == placed.indices.shape
        np.testing.assert_array_equal(np.asarray(z), np.asarray(zp))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s0p))
        print("OK")
        """
    )


def test_solve_on_per_device_blocks_matches_serial_and_float64_reference():
    """solve(fdsvrg_sharded) on per-device blocks of an odd-d set, one
    call and a warm-started second one, against solve(serial) on the
    PaddedCSR (float32, same sample stream: agreement to float32
    rounding of two differently ordered but equal sums, as the other
    sharded-driver tests hold it) and against SVRG in float64 numpy
    (the float32 program's rounding over a few hundred steps of unit-norm
    rows: objectives to 1e-5 relative, iterates to 1e-4 of their norm)."""
    _run(
        """
        from repro.api import ExperimentSpec, solve
        from repro.core import losses

        data = ragged(n=80, dim=1027, seed=5)
        _, bd = per_device(data)
        eta, lam, u, m, seeds = 0.5, 1e-3, 4, 30, (11, 12)
        common = dict(eta=eta, inner_steps=m, batch_size=u, outer_iters=2,
                      reg=losses.l2(lam))
        sh, ser, w_sh, w_ser = [], [], None, None
        for s in seeds:
            a = solve(ExperimentSpec(method="fdsvrg_sharded", data=bd, mesh=MESH,
                                     seed=s, init_w=w_sh, **common))
            b = solve(ExperimentSpec(method="serial", data=data, seed=s,
                                     init_w=w_ser, **common))
            w_sh, w_ser = a.w, b.w
            sh += [h.objective for h in a.history]
            ser += [h.objective for h in b.history]
        assert w_sh.shape == (1027,)
        assert len(w_sh.sharding.device_set) == 4
        np.testing.assert_allclose(sh, ser, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w_sh), np.asarray(w_ser),
                                   rtol=2e-4, atol=2e-6)

        # Float64 SVRG, Option I, on the dense rows, same sample stream.
        X = np.zeros((80, 1027))
        ids, vals = np.asarray(data.indices), np.asarray(data.values, np.float64)
        for i in range(80):
            np.add.at(X[i], ids[i], vals[i])
        y = np.asarray(data.labels, np.float64)
        dl = lambda s, yy: -yy / (1.0 + np.exp(yy * s))
        w = np.zeros(1027)
        ref = []
        for s in seeds:
            rng = np.random.default_rng(s)
            for _ in range(2):
                s0 = X @ w
                z = X.T @ (dl(s0, y) / 80)
                for rows in rng.integers(0, 80, size=(m, u), dtype=np.int64):
                    g = X[rows].T @ ((dl(X[rows] @ w, y[rows]) - dl(s0[rows], y[rows])) / u)
                    w = w - eta * (g + z + lam * w)
                ref.append(np.mean(np.logaddexp(0.0, -y * (X @ w))) + 0.5 * lam * w @ w)
        np.testing.assert_allclose(sh, ref, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w_sh, np.float64), w, rtol=0,
                                   atol=1e-4 * np.linalg.norm(w))
        print("OK")
        """
    )


def test_warm_started_solve_reuses_the_layout():
    """A second, warm-started solve() re-indexes nothing: per-device
    blocks keep their one mesh placement, a PaddedCSR's layout comes
    from the shared cache, and the compiled halves are reused; from a
    host zeros start and then from returned iterates, the warm-started
    calls carry the last call's snapshot and compile nothing."""
    _run(
        """
        import tempfile
        from repro import obs
        from repro.api import ExperimentSpec, solve
        from repro.api.cache import BLOCK_CACHE
        from repro.core import fdsvrg_shardmap, losses
        from repro.data import block_csr

        compiled = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, *a, **k: compiled.append(event)
            if event == "/jax/core/compile/backend_compile_duration" else None)

        built = []
        real = block_csr.block_groups
        def counting(slabs):
            built.append(len(slabs))
            return real(slabs)
        block_csr.block_groups = counting

        data = ragged(n=64, dim=1031, seed=2)
        _, bd = per_device(data)
        common = dict(eta=0.5, inner_steps=8, batch_size=4, outer_iters=1,
                      reg=losses.l2(1e-3), mesh=MESH)
        built.clear()
        first = solve(ExperimentSpec(method="fdsvrg_sharded", data=bd, seed=1,
                                     init_w=jnp.zeros((data.dim,)), **common))
        placed = bd.on_mesh(MESH, ("model",))
        compiled.clear()
        obs.reset()
        with jax.profiler.trace(tempfile.mkdtemp()):
            second = solve(ExperimentSpec(method="fdsvrg_sharded", data=bd, seed=2,
                                          init_w=first.w, **common))
            solve(ExperimentSpec(method="fdsvrg_sharded", data=bd, seed=3,
                                 init_w=second.w, **common))
        assert compiled == [], len(compiled)
        assert obs.totals()["counters"]["snapshot0.carried"] == 2
        assert "snapshot0.computed" not in obs.totals()["counters"]
        assert built == [] and bd.on_mesh(MESH, ("model",)) is placed
        assert len(bd._on_mesh) == 1

        first = solve(ExperimentSpec(method="fdsvrg_sharded", data=data, seed=1, **common))
        assert built == [4]
        layout = BLOCK_CACHE.get(data, 4, mesh_partition(data.dim, 4))
        hits = fdsvrg_shardmap._steps.cache_info().hits
        solve(ExperimentSpec(method="fdsvrg_sharded", data=data, seed=2,
                             init_w=first.w, **common))
        assert built == [4]
        assert BLOCK_CACHE.get(data, 4, mesh_partition(data.dim, 4)) is layout
        assert len(layout._on_mesh) == 1
        assert fdsvrg_shardmap._steps.cache_info().hits == hits + 1
        print("OK")
        """
    )


def test_mesh_counters_and_ingest_span_under_the_profiler():
    """Under the profiler the per-device constructor records
    ``ingest.blocks``, each mesh full-gradient dispatch adds the lanes
    of the placed groups over all chips and the stored entries, and each
    epoch adds its M all-reduce steps."""
    _run(
        """
        import tempfile
        from repro import obs
        from repro.api import ExperimentSpec, solve
        from repro.core import losses

        data = ragged(n=64, dim=1031, seed=4)
        obs.reset()
        with jax.profiler.trace(tempfile.mkdtemp()):
            _, bd = per_device(data)
            solve(ExperimentSpec(method="fdsvrg_sharded", data=bd, mesh=MESH, seed=1,
                                 eta=0.5, inner_steps=8, batch_size=4, outer_iters=2,
                                 reg=losses.l2(1e-3)))
        t = obs.totals()
        assert t["spans"]["ingest.blocks"]["count"] == 1
        lanes = bd.on_mesh(MESH, ("model",)).groups.lanes
        assert lanes == 4 * sum(int(i.size) for i in bd.groups[0].indices)
        # The snapshot at w0 and one after each of the 2 epochs.
        assert t["counters"]["full_grad.lanes"] == 3 * lanes
        assert t["counters"]["full_grad.stored"] == 3 * bd.stored
        assert t["counters"]["mesh.allreduce_steps"] == 2 * 8
        print("OK")
        """
    )
