"""The trace reduction, on a synthetic trace with known answers and on a
small trace recorded on a TPU v5e."""

from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    text = (DATA / "synthetic.xspace.txt").read_text()
    return trace.reduce_planes(ProfileData.from_text_proto(text).planes)


def test_window_and_busy_union(synthetic):
    # Window 100 us; ops [5,15] and [12,18] overlap (13 us), [40,50]
    # (10 us), [95,110] clipped to 5 us; an op before the window is out.
    assert synthetic.window_s == pytest.approx(100e-6)
    assert synthetic.busy_s == pytest.approx(28e-6)
    assert synthetic.devices == 1


def test_modules_and_ops(synthetic):
    assert synthetic.module("foo") == (pytest.approx(13e-6), 1)
    assert synthetic.module("jit_bar") == (pytest.approx(10e-6), 1)
    assert synthetic.module("absent") is None
    assert synthetic.ops == pytest.approx(
        {"fusion.A": 20e-6, "fusion.B": 6e-6, "copy.C": 5e-6})


def test_idle_split_by_innermost_host_span(synthetic):
    # Gaps [0,5], [18,40], [50,95]; solve holds [10,60] and, nested in
    # it, engine.margins [20,30].
    assert synthetic.idle == pytest.approx(
        {"none": 40e-6, "solve": 22e-6, "engine.margins": 10e-6})
    assert sum(synthetic.idle.values()) == pytest.approx(
        synthetic.window_s - synthetic.busy_s)
    bd = synthetic.breakdown()
    assert bd["device_ops"][0] == ["fusion.A", pytest.approx(20e-6)]
    assert bd["idle_gaps"][0] == ["none", pytest.approx(40e-6)]


def test_missing_window_span_is_an_error():
    from jax.profiler import ProfileData

    text = (DATA / "synthetic.xspace.txt").read_text().replace(
        "bench.window", "other")
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(ProfileData.from_text_proto(text).planes)


def test_recorded_tpu_trace():
    """A serving window of a few batches traced on a TPU v5e: the margin
    program is found by name and the shares are sane."""
    red = trace.reduce_file(DATA / "recorded.xplane.pb")
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    seconds, runs = red.module("margins_rows")
    # A module's span also holds the short waits between its ops.
    assert runs == 108 and red.busy_s <= seconds < 1.01 * red.busy_s
    assert sum(red.idle.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert "engine.margins" in red.idle or "flush" in red.idle or "submit" in red.idle


class _Tpu:
    platform, device_kind = "tpu", "TPU v5 lite"


def _traced_train_run(text: str):
    """A train.news20 run whose trace is ``text``, as the driver leaves
    it after the window."""
    from jax.profiler import ProfileData

    from conftest import small_cell
    from harness import core

    run = core.Run(small_cell("train.news20"), seed=1, seconds=1.0, trace=True,
                   devices=[_Tpu()], t_start=0.0)
    run.trace = trace.reduce_planes(ProfileData.from_text_proto(text).planes)
    run.memory_peak_bytes = 1
    run.counts.update(traced_outers=2, outer_bytes=1e6, outer_flops=1e5,
                      inner_steps=100)
    return run


def test_listed_metric_without_its_module_fails_the_run():
    """The synthetic trace holds no full-gradient or inner-epoch module:
    the cell lists both, so the run fails instead of leaving them out."""
    from harness import core

    run = _traced_train_run((DATA / "synthetic.xspace.txt").read_text())
    with pytest.raises(core.MetricMissing, match="full_grad_ms.train"):
        run.result()


def test_listed_metrics_read_when_their_modules_are_there():
    text = (DATA / "synthetic.xspace.txt").read_text()
    text = text.replace("jit_foo", "jit__full_grad_blocks").replace(
        "jit_bar", "jit__inner_epoch")
    run = _traced_train_run(text)
    metrics = run.result()["metrics"]
    assert set(metrics) == {"idle_share.train", "full_grad_ms.train",
                            "inner_step_us.train", "mfu.train"}
    assert metrics["full_grad_ms.train"]["value"] == pytest.approx(13e-3)
    assert metrics["inner_step_us.train"]["value"] == pytest.approx(0.1)
