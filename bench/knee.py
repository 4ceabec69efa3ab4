"""Knee sweep of an open-loop serving cell, on the chip.

    python3 bench/knee.py --config <name> --traffic <name> --rates r1,r2,... \
        [--seconds 10] [--seed n]

One process plays an open-loop traffic mix (``bench/traffic/<name>.json``)
against a configuration at each rate in turn (the traffic file's rate
replaced) and prints, per rate, one JSON line: the p50
and p99 latency over the window and over its last quarter, how late the
generator ran, and how long past the window the last answer came.  The
knee is the highest rate whose backlog stays bounded: the last quarter's
p99 no worse than the whole window's and the last answer within a few
deadlines of the window's close.  Lines also go to
``results/knee/<traffic>.<config>.jsonl``.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=20261016)
    args = ap.parse_args(argv)
    base = core.pair_cell(args.config, args.traffic)
    if base.traffic.get("loop") != "open":
        sys.exit("knee: the sweep is of open-loop traffic")
    devices = core.require_chips(base.chips)
    sys.path.insert(0, str(core.ROOT / "src"))
    core.use_compile_cache()
    out_dir = core.ROOT / "results" / "knee"
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{args.traffic}.{args.config}.jsonl", "a") as log:
        for rate in [float(r) for r in args.rates.split(",")]:
            cell = copy.deepcopy(base)
            cell.traffic["rate"] = rate
            run = core.Run(cell, seed=args.seed, seconds=args.seconds,
                           trace=False, devices=devices, t_start=time.monotonic())
            cell.driver.run(run)
            due, lat = run.samples["due"], run.samples["latency_s"]
            answered = np.isfinite(run.samples["margins"])
            tail = due[answered] >= due[0] + 0.75 * args.seconds
            line = {
                "config": args.config, "traffic": args.traffic, "rate": rate, "seconds": args.seconds,
                "attempted": run.attempted, "failed": run.failed,
                "correct": run.correct,
                "p50_ms": 1e3 * float(np.quantile(lat, 0.5)),
                "p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
                "p99_last_quarter_ms": 1e3 * float(np.quantile(lat[tail], 0.99)),
                "lateness_p99_ms": run.counts.get("lateness_p99_ms"),
                "lateness_max_ms": run.counts.get("lateness_max_ms"),
                "past_window_ms": 1e3 * (run.window_s - args.seconds),
                "batches": run.counts["batches"],
                "rows_per_batch": run.counts["completed"] / max(run.counts["batches"], 1),
            }
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
            del run
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
