"""The readers of the mesh training cell (``*.mesh``): None on an
untraced run and on the one-chip cells, and the right numbers from a
table of program totals and a stub trace."""

import types

import pytest

from harness import core, trace, work

MESH_METRICS = ["inner_step_us.mesh", "allreduce_us.mesh", "full_grad_ms.mesh",
                "full_grad_roofline.mesh", "pad_lane_share.mesh",
                "idle_share.mesh", "mfu.mesh"]
PEAK = work.load_peaks("TPU v5 lite")
U, M, Q = 8, 1000, 4
FG = "%psum.7 = f32[350000]{0:T(1024)S(1)} all-reduce(%fusion.16), channel_id=1"
STEP = "%psum.9 = f32[8]{0:T(128)S(1)} all-reduce(%multiply_reduce_fusion.2), channel_id=1"
TOTALS = {"spans": {}, "counters": {"full_grad.lanes": 1000, "full_grad.stored": 900,
                                     "mesh.allreduce_steps": 2 * M}}


def _stub_trace():
    # Per-device sums over 4 devices: 2 inner epochs of 0.5 s a chip,
    # 3 full gradients of 2 s a chip, the step all-reduces 0.04 s a chip.
    return trace.Reduced(
        window_s=10.0, busy_s=9.0,
        modules={"jit_mesh_inner_epoch(123)": [4.0, 8],
                 "jit_mesh_full_grad(456)": [24.0, 12]},
        ops={STEP: 0.16, FG: 0.4, "%fusion.3 = f32[8]{0} fusion(%x)": 1.0},
        idle={}, devices=Q)


def _run(driver="train_mesh", traced=True):
    cell = core.load_cell("train.webspam.4chip")
    traffic = dict(cell.traffic, driver=driver)
    if driver != "train_mesh":
        traffic["loop"] = "closed"
    run = types.SimpleNamespace(
        traffic=traffic, trace=_stub_trace() if traced else None,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        counts={"inner_steps": M, "traced_outers": 2, "outer_bytes": 4e9,
                "outer_flops": 1e9, "full_grad_bytes": 3e9})
    return cell, run


@pytest.fixture
def totals(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "totals", lambda: TOTALS)


@pytest.mark.parametrize("name", MESH_METRICS)
def test_none_untraced_and_on_other_cells(name, totals):
    cell, run = _run(traced=False)
    assert cell.reader(name).read(run) is None
    for driver in ("train", "serve"):
        _, other = _run(driver=driver)
        assert cell.reader(name).read(other) is None


def test_numbers_from_totals_and_stub_trace(totals):
    cell, run = _run()
    read = {name: cell.reader(name).read(run) for name in MESH_METRICS}
    assert read["inner_step_us.mesh"] == pytest.approx(1e6 * 0.5 / M)
    # Only the f32[u] all-reduces count, per chip, over the steps.
    assert read["allreduce_us.mesh"] == pytest.approx(1e6 * 0.16 / Q / (2 * M))
    assert read["full_grad_ms.mesh"] == pytest.approx(2000.0)
    assert read["full_grad_roofline.mesh"] == pytest.approx(
        100 * 3e9 / PEAK["hbm_bytes_per_s"] / 2.0)
    assert read["pad_lane_share.mesh"] == pytest.approx(10.0)
    assert read["idle_share.mesh"] == pytest.approx(10.0)
    assert read["mfu.mesh"] == pytest.approx(
        100 * 2 * 4e9 / PEAK["hbm_bytes_per_s"] / 10.0)


def test_no_counters_reads_none(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "totals", lambda: {"spans": {}, "counters": {}})
    cell, run = _run()
    assert cell.reader("allreduce_us.mesh").read(run) is None
    assert cell.reader("pad_lane_share.mesh").read(run) is None
    assert cell.reader("full_grad_ms.mesh").read(run) == pytest.approx(2000.0)
