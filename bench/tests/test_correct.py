"""What decides ``correct``, at a size a test run holds: sound runs pass
their limits, the control (the reference in bfloat16 in the program's
place) fails them, and a run whose timed path is broken underneath comes
out not correct, once for each fault a cell can have.  (The cells run on
one chip, so no exchange between chips can be left out.)"""

import numpy as np
import pytest

from conftest import cpu_run, small_cell


def _over(readings: dict, limits: dict) -> list[str]:
    return [k for k, v in readings.items() if not v <= limits[k]]


@pytest.mark.parametrize("name", ["train.news20", "serve.avazu", "serve.news20"])
def test_sound_run_is_correct_and_control_is_not(name):
    run = cpu_run(name, seconds=0.3)
    assert run.correct, run.checks
    limits = {k: c["limit"] for k, c in run.checks.items()}
    readings = run.cell.driver.controls(run)
    assert _over(readings["bfloat16"], limits), readings


def test_training_faults_read_above_the_limits():
    run = cpu_run("train.news20", seconds=0.3)
    limits = {k: c["limit"] for k, c in run.checks.items()}
    readings = run.cell.driver.controls(run)
    assert _over(readings["half_batch"], limits), readings
    assert _over(readings["cold_start"], limits), readings
    assert not _over(readings["float32"], limits), readings


class _Frozen:
    """A solve() whose steps return their state unchanged."""

    def __init__(self, spec):
        import jax.numpy as jnp

        from repro.core.driver import OuterRecord

        self.w = jnp.zeros((spec.data.dim,)) if spec.init_w is None else spec.init_w
        s = float(np.log(2.0))
        self.history = [OuterRecord(t, s, 1.0, 0, 0, 0.0, 0.0)
                        for t in range(spec.outer_iters)]


def test_state_left_unchanged_is_not_correct(monkeypatch):
    cell = small_cell("train.news20")
    monkeypatch.setattr(cell.driver, "solve", _Frozen)
    run = cpu_run("train.news20", cell=cell, seconds=0.3)
    assert not run.correct
    assert run.checks["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(monkeypatch):
    """The program's mini-batch cut to its first half, the mean taken
    over that half: solve() is handed a spec of batch_size u / 2."""
    cell = small_cell("train.news20")
    real = cell.driver.solve

    def half(spec):
        return real(spec.replace(batch_size=spec.batch_size // 2,
                                 inner_steps=spec.data.num_instances // spec.batch_size))

    monkeypatch.setattr(cell.driver, "solve", half)
    run = cpu_run("train.news20", cell=cell, seconds=0.3)
    assert not run.correct, run.checks


def test_warm_start_ignored_is_not_correct(monkeypatch):
    """solve() drops init_w, so every call starts again from w = 0: the
    checked calls are the window's own warm-started ones, so the second
    of them departs from the reference."""
    cell = small_cell("train.news20")
    real = cell.driver.solve

    def cold(spec):
        return real(spec.replace(init_w=None))

    monkeypatch.setattr(cell.driver, "solve", cold)
    run = cpu_run("train.news20", cell=cell, seconds=0.3)
    assert not run.correct, run.checks
    assert run.checks["loss_gap"]["value"] > run.checks["loss_gap"]["limit"]


def test_checked_calls_are_the_windows_own(monkeypatch):
    """Set-up and window calls alike pass init_w and outers_per_call
    outers, each with a seed of its own."""
    cell = small_cell("train.news20")
    real = cell.driver.solve
    seen = []

    def spy(spec):
        seen.append((spec.init_w is not None, spec.outer_iters, spec.seed))
        return real(spec)

    monkeypatch.setattr(cell.driver, "solve", spy)
    run = cpu_run("train.news20", cell=cell, seconds=0.3)
    assert run.correct, run.checks
    per_call = cell.traffic["outers_per_call"]
    assert len(seen) == cell.traffic["check_calls"] + run.counts["calls"]
    assert all(given and outers == per_call for given, outers, _ in seen)
    assert len({s for _, _, s in seen}) == len(seen)


@pytest.mark.parametrize("name", ["serve.avazu", "serve.avazu.bulk", "serve.news20"])
def test_serving_faults_are_not_correct(monkeypatch, name):
    from repro.serve.engine import PredictionEngine

    real = PredictionEngine.margins

    def half_left_out(self, indices, values, **kw):
        out = real(self, indices, values, **kw)
        return out[: (len(out) + 1) // 2] if len(out) > 1 else out

    def one_altered(self, indices, values, **kw):
        out = np.array(real(self, indices, values, **kw))
        out[0] += 1e-3 * (1.0 + abs(out[0]))
        return out

    for broken in (half_left_out, one_altered):
        monkeypatch.setattr(PredictionEngine, "margins", broken)
        run = cpu_run(name, seconds=0.3)
        assert not run.correct, (broken.__name__, run.checks, run.failed)
    monkeypatch.setattr(PredictionEngine, "margins", real)
    assert cpu_run(name, seconds=0.3).correct
