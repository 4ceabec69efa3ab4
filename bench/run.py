"""Run one cell of the benchmark once, on the chip it was started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data and weights made on the device from ``--seed``, every shape
of the cell warmed) is followed by a window of ``--seconds`` seconds;
after it the served outputs are compared with the plain reference.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; its last key, ``checks``, holds each number compared with
its limit, as do the last lines of standard error.  Without a TPU, with
fewer chips than the cell asks for, or when a metric the cell lists
finds nothing to read, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import core  # noqa: E402


def main(argv=None) -> int:
    t_start = core.process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = core.load_cell(args.workload)
    devices = core.require_chips(cell.chips)
    sys.path.insert(0, str(core.ROOT / "src"))
    core.use_compile_cache()
    run = core.Run(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, t_start=t_start)
    cell.driver.run(run)
    result = missing = None
    try:
        result = run.result()
    except core.MetricMissing as e:
        missing = e
    print("counts " + json.dumps(run.counts), file=sys.stderr)
    if missing is not None:
        print(f"bench: {missing}", file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    if missing is not None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
