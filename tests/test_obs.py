"""`repro.obs`: program spans and counters, recorded only under the profiler.

* with the profiler off, nothing is built and nothing is recorded;
* under ``jax.profiler.trace`` spans record their parent, ids and self
  time, counters add up, and each span's duration is the duration of
  the host event of the same name in the xplane (one clock);
* ``solve()`` and one served batch record the documented span trees.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import ExperimentSpec, solve
from repro.data.synthetic import make_sparse_classification
from repro.serve import MicroBatcher, PredictionEngine, WeightSnapshot


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _host_events(directory):
    """Host events of the xplane under ``directory``: name -> [(ns, stats)]."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.duration_ns, dict(ev.stats)))
    return events


def test_profiler_off_records_nothing_and_builds_no_annotation(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("built a TraceAnnotation with the profiler off")

    monkeypatch.setattr(obs, "TraceAnnotation", boom)
    monkeypatch.setattr(obs, "StepTraceAnnotation", boom)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("a", batch=1) as a, obs.span("b", step_num=0) as b:
        obs.count("c", 5)
    assert a is b  # the shared no-op
    obs.end("a")
    assert obs.records() == []
    assert obs.totals() == {"spans": {}, "counters": {}}


def test_spans_record_parent_ids_self_time_and_counters(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("top", step_num=3, attempt=1):
            time.sleep(0.004)
            with obs.span("top.child", batch=7):
                time.sleep(0.008)
            obs.count("lanes", 10)
            obs.count("lanes", 22)
            obs.count("stored", 5)
    by = _by_name(obs.records())
    (top,), (child,) = by["top"], by["top.child"]
    assert top.parent is None and child.parent == "top"
    assert top.ids == {"step_num": 3, "attempt": 1} and child.ids == {"batch": 7}
    assert top.child_ns == child.end_ns - child.start_ns
    assert top.start_ns <= child.start_ns < child.end_ns <= top.end_ns
    tot = obs.totals()
    assert tot["counters"] == {"lanes": 32, "stored": 5}
    t, c = tot["spans"]["top"], tot["spans"]["top.child"]
    assert t["count"] == c["count"] == 1
    assert c["self_seconds"] == pytest.approx(c["seconds"])
    assert t["self_seconds"] == pytest.approx(t["seconds"] - c["seconds"])
    assert t["self_seconds"] >= 0.004 and c["seconds"] >= 0.008
    # Recording is decided at entry: nothing after the profiler stops.
    with obs.span("late"):
        obs.count("lanes", 1)
    assert "late" not in obs.totals()["spans"]
    assert obs.totals()["counters"]["lanes"] == 32


def test_span_durations_match_the_xplane_host_events(tmp_path):
    """One clock: a recorded span lasts what its host event lasts."""
    with jax.profiler.trace(str(tmp_path)):
        for k, pause in enumerate((0.0, 0.002, 0.015)):
            with obs.span(f"clock.{k}", batch=k):
                time.sleep(pause)
                with obs.span(f"clock.{k}.inner"):
                    jax.block_until_ready(jax.numpy.ones(16) * k)
    events = _host_events(tmp_path)
    records = obs.records()
    assert len(records) == 6
    for r in records:
        (dur_ns, stats), = events[r.name]
        assert {k: stats[k] for k in r.ids} == r.ids
        mine = r.end_ns - r.start_ns
        assert abs(mine - dur_ns) <= max(0.02 * dur_ns, 20_000), r.name


def test_solve_records_its_span_tree_and_counters(tmp_path):
    data = make_sparse_classification(
        dim=300, num_instances=64, nnz_per_instance=6, seed=1)
    spec = ExperimentSpec(method="fdsvrg", data=data, q=2, outer_iters=2,
                          inner_steps=8, batch_size=2, seed=3,
                          checkpoint_dir=str(tmp_path / "ckpt"))
    with jax.profiler.trace(str(tmp_path / "trace")):
        solve(spec)
    by = _by_name(obs.records())
    for name in ("solve", "solve.prepare", "loop.snapshot0"):
        assert len(by[name]) == 1, name
    assert by["solve.prepare"][0].parent == "solve"
    assert by["loop.snapshot0"][0].parent == "solve"
    # solve.prepare ends where the outer loop starts.
    assert by["solve.prepare"][0].end_ns <= by["loop.snapshot0"][0].start_ns
    outers = by["outer"]
    assert [r.ids for r in outers] == [{"step_num": 0, "attempt": 0},
                                       {"step_num": 1, "attempt": 0}]
    assert all(r.parent == "solve" for r in outers)
    for child in ("outer.samples", "outer.epoch", "outer.snapshot",
                  "outer.evaluate", "outer.checkpoint"):
        assert len(by[child]) == 2, child
        assert all(r.parent == "outer" for r in by[child])
        for r, o in zip(by[child], outers):
            assert o.start_ns <= r.start_ns <= r.end_ns <= o.end_ns
    # Three full gradients (snapshot0 and one an outer), each counted.
    from repro.api.cache import BLOCK_CACHE

    bd = BLOCK_CACHE.get(data, 2)
    counters = obs.totals()["counters"]
    # Lanes of the row groups the full gradient walks: sum of N_b * W_b.
    assert counters["full_grad.lanes"] == 3 * sum(
        idx.shape[0] * idx.shape[1] for g in bd.groups for idx in g.indices)
    assert counters["full_grad.stored"] == 3 * int(np.count_nonzero(
        np.asarray(data.values)))


def test_one_served_batch_records_its_spans_under_one_batch_id(tmp_path):
    engine = PredictionEngine(WeightSnapshot.from_dense(
        np.linspace(-1, 1, 64, dtype=np.float32), 0))
    batcher = MicroBatcher(max_batch=4, max_delay_s=0.0)
    batcher.submit(np.array([1, 5, 9]), np.ones(3, np.float32))
    batcher.submit(np.array([2, 3]), np.ones(2, np.float32))
    (warm,) = batcher.drain()
    engine.margins(warm.indices, warm.values)  # compile outside the trace
    batcher.submit(np.array([7, 8, 40]), np.ones(3, np.float32))
    batcher.submit(np.array([0]), np.ones(1, np.float32))
    with jax.profiler.trace(str(tmp_path)):
        (batch,) = batcher.drain()
        engine.margins(batch.indices, batch.values, batch=batch.seq)
    assert batch.seq == warm.seq + 1
    by = _by_name(obs.records())
    assert sorted(by) == ["serve.d2h", "serve.dispatch", "serve.engine",
                          "serve.h2d", "serve.pack"]
    assert all(len(v) == 1 for v in by.values())
    (pack,), (eng,) = by["serve.pack"], by["serve.engine"]
    assert pack.ids == {"batch": batch.seq, "rows": 2, "width": 8, "valid": 2}
    assert eng.ids == {"batch": batch.seq}
    for child in ("serve.h2d", "serve.dispatch", "serve.d2h"):
        assert by[child][0].parent == "serve.engine"
    assert pack.end_ns <= eng.start_ns
    t = obs.totals()["spans"]["serve.engine"]
    parts = sum(obs.totals()["spans"][c]["seconds"]
                for c in ("serve.h2d", "serve.dispatch", "serve.d2h"))
    assert t["self_seconds"] == pytest.approx(t["seconds"] - parts)
