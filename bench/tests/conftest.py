"""Harness tests: run on the CPU at small sizes, never on the chip.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They are not part of the repository's tier-1 suite (whose ``testpaths``
is ``tests``).
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import core  # noqa: E402

# Small stand-ins for the configurations: every key of the real file,
# with the scale cut so that a cell runs in seconds on a CPU.
SMALL = {
    "news20": {"dim": 20000, "num_instances": 512, "nnz_per_row": 24,
               "lengths": {"dist": "lognormal", "median": 21.5, "sigma": 0.5,
                           "min": 7, "max": 64}},
    "avazu": {"dim": 15000},
}
SMALL_TRAFFIC = {
    "train": {"trace_seconds": 0.5},
    "steady.avazu": {"rate": 2000.0, "pool": 2048, "trace_seconds": 0.5},
    "bulk": {"outstanding": 64, "pool": 2048, "trace_seconds": 0.5},
    "steady.news20": {"rate": 500.0, "pool": 256, "trace_seconds": 0.5,
                      "lengths": {"dist": "lognormal", "median": 20.0,
                                  "sigma": 0.5, "min": 9, "max": 64}},
}


# Open-loop cells that BENCHMARK.json does not list yet: (config, traffic).
UNLISTED = {"serve.avazu": ("avazu", "steady.avazu"),
            "serve.news20": ("news20", "steady.news20")}


def small_cell(name: str) -> core.Cell:
    cell = core.pair_cell(*UNLISTED[name]) if name in UNLISTED else core.load_cell(name)
    cell = copy.deepcopy(cell)
    cell.config.update(SMALL[cell.config["name"]])
    cell.traffic.update(SMALL_TRAFFIC[cell.traffic_name])
    return cell


def cpu_run(name: str, *, seed: int = 2**31 + 7, seconds: float = 1.0,
            trace: bool = False, cell: core.Cell | None = None):
    """Drive one small run past the look for a chip; returns the Run."""
    import jax

    cell = cell or small_cell(name)
    run = core.Run(cell, seed=seed, seconds=seconds, trace=trace,
                   devices=jax.devices()[:1])
    cell.driver.run(run)
    return run


@pytest.fixture
def run_cell():
    return cpu_run
