"""The mesh training cell at a size a test run holds, on 4 forced host
devices in a subprocess (the test process keeps its one device): the
rows module's blocks, the cell's ``correct`` and its control and fault,
and the stop on a program that cannot take per-chip blocks."""

import os
import subprocess
import sys
import textwrap

from harness import core

_COMMON = textwrap.dedent(
    """
    import copy, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
    import jax, numpy as np
    from harness import core
    from repro.dist import make_mesh

    SMALL = dict(dim=20011, num_instances=512, nnz_per_row=120,
                 lengths=dict(dist="lognormal", median=107.7, sigma=0.5, min=34, max=270))
    cell = copy.deepcopy(core.load_cell("train.webspam.4chip"))
    cell.config.update(SMALL)
    cell.traffic.update(trace_seconds=0.5, inner_steps="paper")
    web = cell.rows
    """
)


def _run(body: str) -> str:
    code = _COMMON.format(bench=str(core.BENCH), src=str(core.ROOT / "src"))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code + textwrap.dedent(body)],
                          capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout, proc.stdout[-2000:]
    return proc.stdout


def test_blocks_rejoined_are_the_stated_rows_whatever_the_chunking():
    """Each chip's block holds its share of every row; rejoined, a row
    has unique ids, its stated length and unit norm, and a seed gives
    the same rows however they are chunked."""
    _run(
        """
        mesh = make_mesh((4,), ("model",))
        seed = 2**40 + 3
        blocks, labels, lengths, shares = web.blocks(cell.config, seed, mesh)
        bounds = web.bounds(cell.config["dim"], 4)
        ids = [np.asarray(i) + lo for (i, _), lo in zip(blocks, bounds)]
        vals = [np.asarray(v) for _, v in blocks]
        for (i, v), d in zip(blocks, mesh.devices.flat):
            assert i.devices() == v.devices() == {d}
        np.testing.assert_array_equal(shares.sum(axis=1), lengths)
        assert lengths.sum() == 512 * 120
        norm = sum((v.astype(np.float64) ** 2).sum(axis=1) for v in vals)
        np.testing.assert_allclose(norm, 1.0, rtol=1e-5)
        for r in range(512):
            row = np.concatenate([i[r][v[r] != 0] for i, v in zip(ids, vals)])
            assert row.size == lengths[r] and np.unique(row).size == row.size
            assert row.max() < cell.config["dim"]
        assert set(np.unique(np.asarray(labels))) <= {-1.0, 1.0}
        web.CHUNK_DRAWS = 1 << 12
        again, labels2, _, _ = web.blocks(cell.config, seed, mesh)
        for (a, b), (c, d) in zip(blocks, again):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(d))
        np.testing.assert_array_equal(np.asarray(labels), np.asarray(labels2))
        print("OK")
        """
    )


def test_sound_run_is_correct_and_control_and_fault_are_not():
    """The cell's run passes its limits; the reference in bfloat16 in
    the program's place and with half of each mini-batch left out each
    fail at least one."""
    out = _run(
        """
        run = core.Run(cell, seed=2**33 + 5, seconds=0.3, trace=False,
                       devices=jax.devices()[:4])
        cell.driver.run(run)
        assert run.correct, run.checks
        assert run.counts["rows_visited"] > 0
        limits = {k: c["limit"] for k, c in run.checks.items()}
        readings = cell.driver.controls(run)
        over = lambda side: [k for k, v in readings[side].items() if not v <= limits[k]]
        assert over("bfloat16"), readings
        assert over("half_batch"), readings
        print("OK", readings)
        """
    )
    assert "bfloat16" in out


def test_program_without_per_chip_blocks_stops_before_any_data():
    """A program whose BlockCSR has no from_blocks (as before this cell)
    ends the run with a non-zero exit before any data is made."""
    _run(
        """
        from repro.data.block_csr import BlockCSR
        del BlockCSR.from_blocks
        made = []
        web.blocks = lambda *a, **k: made.append(1)
        run = core.Run(cell, seed=1, seconds=0.3, trace=False, devices=jax.devices()[:4])
        try:
            cell.driver.run(run)
        except SystemExit as e:
            assert e.code not in (0, None) and not made
            print("OK")
        """
    )
