"""Roofline table: renders results/dryrun/*.json into the EXPERIMENTS.md
§Roofline table (one row per arch x shape x mesh).

When results/dryrun/ is empty (a fresh checkout), :func:`ensure_results`
populates it by running ONE reduced arch x mesh combo through
``repro.launch.dryrun --smoke`` — in a subprocess, because dryrun must
set XLA_FLAGS (host device count) before jax initializes, which is
impossible once this process has imported jax.  So the table always
measures at least one real compiled combo instead of silently rendering
zero rows."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

from benchmarks.common import write_bench_json, write_csv
from repro.launch.roofline import per_program

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def load_results() -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(os.path.abspath(DRYRUN_DIR), "*.json"))):
        with open(path) as f:
            out.extend(per_program(json.load(f)))
    return out


def ensure_results(timeout: float = 600.0) -> None:
    """Populate an empty results/dryrun/ with the --smoke combo."""
    if load_results():
        return
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    # A CPU lowering of the LM stack: the child must not ask for the TPU
    # this (JAX-importing) parent already holds.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke",
         "--out-dir", os.path.abspath(DRYRUN_DIR)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"dryrun --smoke failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )


def run():
    ensure_results()
    rows = []
    for r in load_results():
        if not r.get("ok"):
            rows.append([r.get("arch"), r.get("shape"), r.get("mesh"),
                         "FAIL", "", "", "", "", "", r.get("error", "")[:120]])
            continue
        rl = r.get("roofline")
        if not rl:  # multi-pod proof row: lower+compile only
            rows.append([
                r["arch"], r["shape"], r["mesh"], "proof", "", "", "", "", "",
                f"compiled in {r.get('compile_s', '?')}s",
            ])
            continue
        ratio = r.get("useful_flops_ratio")
        rows.append([
            r["arch"], r["shape"], r["mesh"],
            f"{rl['compute_s']:.5f}", f"{rl['memory_s']:.5f}",
            f"{rl['collective_s']:.5f}", rl["dominant"],
            f"{r.get('model_flops', 0):.3e}",
            f"{ratio:.3f}" if ratio else "",
            f"temp={r.get('memory_analysis', {}).get('temp_size_in_bytes', 0)/2**30:.2f}GiB",
        ])
    path = write_csv(
        "roofline_table.csv",
        ["arch", "shape", "mesh", "compute_s", "memory_s", "collective_s",
         "dominant", "model_flops", "useful_flops_ratio", "memory"],
        rows,
    )
    return path, rows


def markdown_table() -> str:
    _, rows = run()
    hdr = ("| arch | shape | mesh | compute_s | memory_s | collective_s | "
           "dominant | MODEL_FLOPS | useful ratio | mem |")
    sep = "|" + "---|" * 10
    lines = [hdr, sep]
    for r in rows:
        lines.append("| " + " | ".join(str(c) for c in r) + " |")
    return "\n".join(lines)


def main():
    path, rows = run()
    print(f"roofline: wrote {len(rows)} rows to {path}")
    ok = sum(1 for r in rows if r[3] != "FAIL")
    print(f"  {ok}/{len(rows)} combos OK")
    write_bench_json("roofline", {"ok": ok, "total": len(rows)})


if __name__ == "__main__":
    main()
