"""Readings the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 3]

One process: for each seed, one run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the comparison with the
reference), printing the numbers compared; for each control seed also
the readings of the control (the reference in the next precision down,
in the program's place) and of the faults the cell can have.  Each
reading is one JSON line on standard output and in
``results/calibrate/<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = core.load_cell(args.workload)
    devices = core.require_chips(cell.chips)
    sys.path.insert(0, str(core.ROOT / "src"))
    core.use_compile_cache()
    out_dir = core.ROOT / "results" / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    with open(out_dir / f"{args.workload}.jsonl", "a") as log:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.monotonic()
            run = core.Run(cell, seed=seed, seconds=args.seconds, trace=False,
                           devices=devices, t_start=t)
            cell.driver.run(run)
            line = {"workload": args.workload, "seed": seed, "side": "program",
                    "readings": {k: c["value"] for k, c in run.checks.items()},
                    "failed": run.failed, "attempted": run.attempted,
                    "wall_s": time.monotonic() - t}
            if "ref" in run.samples:
                line["reference_objectives"] = run.samples["ref"]["objectives"]
            rows = [line]
            if seed in controls:
                for side, readings in cell.driver.controls(run).items():
                    rows.append({"workload": args.workload, "seed": seed,
                                 "side": side, "readings": readings})
            for r in rows:
                print(json.dumps(r), flush=True)
                log.write(json.dumps(r) + "\n")
            del run
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
