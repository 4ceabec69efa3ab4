"""Readers of the program's own spans and counters (``harness.spans``):
None where there is nothing to read, the right numbers from a totals
table, and sane numbers from small traced runs on the CPU."""

import sys
import types

import pytest

from conftest import small_cell
from harness import core

TRAIN = ("solve_prepare_ms.train", "samples_ms.train", "pad_lane_share.train")
BULK = ("pack_us.bulk", "h2d_us.bulk", "dispatch_us.bulk", "d2h_us.bulk",
        "engine_self_us.bulk")


class _Tpu:
    platform, device_kind = "tpu", "TPU v5 lite"


def _run(cell: str, *, traced: bool = True):
    run = core.Run(small_cell(cell), seed=1, seconds=1.0, trace=traced,
                   devices=[_Tpu()], t_start=0.0)
    if traced:
        run.trace = types.SimpleNamespace(devices=1, window_s=1.0, busy_s=0.5)
    return run


def _read(run, metric: str):
    return run.cell.reader(metric).read(run)


TABLE = {
    "spans": {
        "solve": {"count": 2, "seconds": 2.0, "self_seconds": 0.01},
        "solve.prepare": {"count": 2, "seconds": 0.030, "self_seconds": 0.030},
        "outer": {"count": 4, "seconds": 1.9, "self_seconds": 0.002},
        "outer.samples": {"count": 4, "seconds": 0.008, "self_seconds": 0.008},
        "serve.pack": {"count": 10, "seconds": 0.004, "self_seconds": 0.004},
        "serve.engine": {"count": 8, "seconds": 0.016, "self_seconds": 0.0016},
        "serve.h2d": {"count": 8, "seconds": 0.0024, "self_seconds": 0.0024},
        "serve.dispatch": {"count": 8, "seconds": 0.004, "self_seconds": 0.004},
        "serve.d2h": {"count": 8, "seconds": 0.008, "self_seconds": 0.008},
    },
    "counters": {"full_grad.lanes": 3 * 1024, "full_grad.stored": 3 * 455},
}


@pytest.fixture
def table(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "totals", lambda: TABLE)
    return TABLE


@pytest.mark.parametrize("cell,metrics", [("train.news20", TRAIN),
                                          ("serve.avazu.bulk", BULK)])
def test_none_untraced_and_on_the_other_kind(table, cell, metrics):
    other = BULK if metrics is TRAIN else TRAIN
    for m in metrics:
        assert _read(_run(cell, traced=False), m) is None
    for m in other:
        assert _read(_run(cell), m) is None


def test_train_numbers_from_a_totals_table(table):
    run = _run("train.news20")
    assert _read(run, "solve_prepare_ms.train") == pytest.approx(15.0)
    assert _read(run, "samples_ms.train") == pytest.approx(2.0)
    assert _read(run, "pad_lane_share.train") == pytest.approx(
        100 * (1 - 455 / 1024))


def test_bulk_numbers_from_a_totals_table(table):
    run = _run("serve.avazu.bulk")
    got = {m: _read(run, m) for m in BULK}
    assert got == pytest.approx({"pack_us.bulk": 400.0, "h2d_us.bulk": 300.0,
                                 "dispatch_us.bulk": 500.0, "d2h_us.bulk": 1000.0,
                                 "engine_self_us.bulk": 200.0})
    # The parts add up to the engine span's mean.
    assert sum(v for m, v in got.items() if m != "pack_us.bulk") == \
        pytest.approx(1e6 * 0.016 / 8)


def test_none_where_a_span_or_counter_recorded_nothing(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "totals", lambda: {"spans": {}, "counters": {}})
    for m in TRAIN:
        assert _read(_run("train.news20"), m) is None
    for m in BULK:
        assert _read(_run("serve.avazu.bulk"), m) is None


def test_none_on_a_program_without_obs(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for m in TRAIN:
        assert _read(_run("train.news20"), m) is None
    for m in BULK:
        assert _read(_run("serve.avazu.bulk"), m) is None


def test_small_traced_runs_read_their_spans(run_cell):
    """Traced CPU runs: the profiler's window bounds what is recorded.
    The CPU trace has no device plane, so the run is told it saw one."""
    from repro import obs

    obs.reset()
    run = run_cell("serve.avazu.bulk", trace=True)
    run.trace.devices = 1
    got = {m: _read(run, m) for m in BULK}
    assert all(v is not None and v > 0 for v in got.values()), got
    engine_us = 1e3 * _read(run, "engine_ms.bulk")
    inside = sum(v for m, v in got.items() if m != "pack_us.bulk")
    assert 0.5 * engine_us < inside <= engine_us
    spans = obs.totals()["spans"]
    assert spans["serve.engine"]["count"] == run.counts["traced_batches"]

    obs.reset()
    run = run_cell("train.news20", trace=True)
    run.trace.devices = 1
    assert _read(run, "solve_prepare_ms.train") > 0
    assert _read(run, "samples_ms.train") > 0
    share = _read(run, "pad_lane_share.train")
    counters = obs.totals()["counters"]
    assert counters["full_grad.lanes"] > counters["full_grad.stored"] > 0
    assert 0 < share < 100
    spans = obs.totals()["spans"]
    assert spans["loop.snapshot0"]["count"] == spans["solve"]["count"]
    assert spans["outer"]["count"] == run.counts["traced_outers"]
    obs.reset()
