"""Configurations, traffic mixes and metrics are found by file name: a
new one is a new file, and no file that is there changes."""

import json
import shutil
import subprocess
import sys

import pytest

from harness import core


def test_every_name_in_benchmark_json_has_its_files():
    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = core.load_cell(w["name"])
        assert cell.driver.run and cell.rows.rows and cell.reference.margins
        for m in cell.metrics(False) + cell.metrics(True):
            assert callable(cell.reader(m["name"]).read)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (core.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = core.load_cell(w["name"])
        e2e = [m["name"] for m in cell.metrics(False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics(True)


def test_added_files_are_found_without_edits(tmp_path):
    shutil.copytree(core.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    cfg = core.load_json(core.BENCH / "configs" / "avazu.json")
    cfg["name"] = "avazu_wide"
    (tmp_path / "bench" / "configs" / "avazu_wide.json").write_text(json.dumps(cfg))
    traffic = core.load_json(core.BENCH / "traffic" / "steady.avazu.json")
    traffic["burst"] = {"on_s": 0.5, "off_s": 0.5}
    (tmp_path / "bench" / "traffic" / "bursty.json").write_text(json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "burst_p99_ms.py").write_text(
        "def read(run):\n    return 1.0\n")
    spec["configs"].append({"name": "avazu_wide", "source": "x",
                            "file": "bench/configs/avazu_wide.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "serve.avazu_wide.bursty",
                              "config": "avazu_wide", "traffic": "bursty",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "burst_p99_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "repro.serve.batching",
                              "moves": "serve_preds_per_s",
                              "workloads": ["serve.avazu_wide.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = core.load_cell("serve.avazu_wide.bursty", root=tmp_path)
    assert cell.config["name"] == "avazu_wide"
    assert cell.traffic["burst"] == {"on_s": 0.5, "off_s": 0.5}
    assert cell.driver.__file__.startswith(str(tmp_path))
    names = [m["name"] for m in cell.metrics(True)]
    assert "burst_p99_ms" in names and "idle_share.train" not in names
    assert cell.reader("burst_p99_ms").read(None) == 1.0


def test_bursts_keep_the_mean_rate():
    import numpy as np

    arrivals = core.load_module(core.BENCH / "arrivals.py")
    rng = np.random.default_rng(0)
    plain = arrivals.schedule({"rate": 1000.0}, 10.0, rng)
    burst = arrivals.schedule({"rate": 1000.0, "burst": {"on_s": 0.25, "off_s": 0.75}},
                              10.0, rng)
    assert len(plain) == len(burst) == 10000
    assert np.all(np.diff(burst) >= 0) and burst[-1] < 10.0
    phase = np.mod(burst, 1.0)
    assert np.all(phase < 0.25)


def test_refuses_a_cpu(tmp_path):
    """Without a TPU the command exits non-zero and prints no result."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, str(core.BENCH / "run.py"), "--workload", "train.news20",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=core.ROOT, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_seeds_of_any_size_are_reproducible(seed):
    from harness import seeds

    assert (seeds.words(seed, 1) == seeds.words(seed, 1)).all()
    assert (seeds.words(seed, 1) != seeds.words(seed, 2)).any()
    assert 0 <= seeds.int32(seed, 3) < 2**31
