"""Run any registered method on any LinearConfig from the command line.

    PYTHONPATH=src python -m repro.api.cli --config fdsvrg-news20 --method fdsvrg
    PYTHONPATH=src python -m repro.api.cli --list
    PYTHONPATH=src python -m repro.api.cli --config fdsvrg-news20 \\
        --method dsvrg --quick
    PYTHONPATH=src python -m repro.api.cli --data path/to/train.libsvm \\
        --data-cache .ingest_cache --workers 8

One flag per spec knob; everything unset resolves through the registry's
``"paper"`` defaults.  ``--quick`` is the CI smoke shape: 2 outer
iterations with the inner loop capped at 300 steps.

``--data`` streams a LibSVM file through the out-of-core ingestion path
(worker slabs built incrementally, the global matrix never materialized);
combined with ``--config`` it keeps the preset's loss/reg/eta but swaps
the data in.  ``--data-cache`` persists the built slabs so re-runs skip
parsing.
"""

from __future__ import annotations

import argparse
import sys

from repro.api.registry import (
    METHODS,
    PAPER_MAX_INNER,
    capability_matrix,
    method_info,
    solve,
)
from repro.configs.fdsvrg_linear import CONFIGS
from repro.launch.compile_cache import use_compile_cache


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.api.cli",
        description="One front door: solve an ExperimentSpec with any "
        "registered method.",
    )
    p.add_argument("--config", choices=sorted(CONFIGS),
                   help="LinearConfig preset (repro.configs.fdsvrg_linear)")
    p.add_argument("--data", default=None, metavar="PATH",
                   help="stream a LibSVM file instead of a preset's "
                   "synthetic data (out-of-core ingestion; streaming "
                   "methods only)")
    p.add_argument("--data-cache", default=None, metavar="DIR",
                   help="on-disk slab cache for --data (warm re-runs "
                   "skip parsing)")
    p.add_argument("--chunk-rows", type=int, default=None,
                   help="rows per parsed chunk for --data (bounds host "
                   "memory; default 65536)")
    p.add_argument("--method", default="fdsvrg",
                   help=f"registered method ({', '.join(sorted(METHODS))})")
    p.add_argument("--outer-iters", type=int, default=None)
    p.add_argument("--eta", type=float, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--inner-steps", type=int, default=None)
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--use-kernels", action="store_true")
    p.add_argument("--lazy-updates", choices=("exact", "proba"), default=None,
                   help="O(nnz) delayed-decay inner steps (lazy-capable "
                   "methods only)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="persist a rolling outer-loop checkpoint here "
                   "(checkpoint-capable methods only)")
    p.add_argument("--checkpoint-every", type=int, default=None,
                   help="outers between checkpoint writes (default 1)")
    p.add_argument("--resume", action="store_true",
                   help="resume from --checkpoint-dir when a checkpoint "
                   "exists (bit-identical to the uninterrupted run)")
    p.add_argument("--quick", action="store_true",
                   help="CI smoke shape: 2 outers, inner loop capped at 300")
    p.add_argument("--list", action="store_true",
                   help="print the method registry (capability matrix) and exit")
    return p


def _print_registry() -> None:
    """Render repro.api.capability_matrix() — ONE source for this table
    and the docs: a new MethodInfo capability shows up here for free."""
    rows = sorted(capability_matrix(), key=lambda r: r["method"])
    cols = list(rows[0])
    widths = {
        c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols[:-1]
    }
    print(" ".join([f"{c:<{widths[c]}}" for c in cols[:-1]] + [cols[-1]]))
    for r in rows:
        print(" ".join([f"{str(r[c]):<{widths[c]}}" for c in cols[:-1]]
                       + [str(r[cols[-1]])]))


def main(argv: list[str] | None = None) -> int:
    args = _parser().parse_args(argv)
    use_compile_cache()
    if args.list:
        _print_registry()
        return 0
    if args.config is None and args.data is None:
        print("error: --config or --data is required (or use --list)",
              file=sys.stderr)
        return 2
    try:
        info = method_info(args.method)  # fail fast on unknown methods
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    lc = CONFIGS[args.config] if args.config is not None else None

    overrides: dict = {}
    if args.data is not None:
        overrides["dataset"] = None  # the source replaces any preset data
        overrides["source"] = args.data
        if args.data_cache is not None:
            overrides["data_cache_dir"] = args.data_cache
        if args.chunk_rows is not None:
            overrides["ingest_chunk_rows"] = args.chunk_rows
    elif args.data_cache is not None or args.chunk_rows is not None:
        print("error: --data-cache/--chunk-rows only apply with --data",
              file=sys.stderr)
        return 2
    if args.outer_iters is not None:
        overrides["outer_iters"] = args.outer_iters
    if args.eta is not None:
        overrides["eta"] = args.eta
    if args.batch_size is not None:
        overrides["batch_size"] = args.batch_size
    if args.inner_steps is not None:
        overrides["inner_steps"] = args.inner_steps
    if args.workers is not None:
        overrides["q"] = args.workers
    elif info.needs_mesh and lc is not None:
        # shard_map: the worker count IS the mesh size; drop the config's
        # paper worker count so the default mesh over every local device
        # decides — and say so, because its comm meters reflect that q,
        # not the preset's multi-worker runs.
        overrides["q"] = None
        import jax

        n_dev = jax.local_device_count()
        print(f"note: {args.method} runs at the mesh size (q={n_dev} "
              f"device(s) here), not the preset's workers={lc.workers}; "
              "comm meters reflect that q")
    if args.seed is not None:
        overrides["seed"] = args.seed
    if args.use_kernels:
        overrides["use_kernels"] = True
    if args.lazy_updates is not None:
        overrides["lazy_updates"] = args.lazy_updates
    if args.checkpoint_dir is not None:
        overrides["checkpoint_dir"] = args.checkpoint_dir
    if args.checkpoint_every is not None:
        overrides["checkpoint_every"] = args.checkpoint_every
    if args.resume:
        overrides["resume"] = True
    if args.quick:
        overrides.setdefault("outer_iters", 2)
        overrides.setdefault("inner_steps", min(300, PAPER_MAX_INNER))

    if lc is not None:
        data_desc = (
            f"data={args.data}" if args.data else f"dataset={lc.dataset}"
        )
        print(f"config {lc.name}: {data_desc} method={args.method} "
              f"({info.summary})")
        make_spec = lambda: lc.to_spec(method=args.method, **overrides)
    else:
        from repro.api.spec import ExperimentSpec

        overrides.pop("dataset", None)
        print(f"data {args.data}: method={args.method} ({info.summary})")
        make_spec = lambda: ExperimentSpec(method=args.method, **overrides)
    try:
        result = solve(make_spec())
    except (TypeError, ValueError) as e:
        # spec/capability validation errors follow the CLI's one-line
        # error convention, same as a missing --config
        print(f"error: {e}", file=sys.stderr)
        return 2

    print(f"\n{'outer':>5} {'objective':>12} {'optimality':>12} "
          f"{'comm scalars':>13} {'modeled s':>10}")
    for h in result.history:
        print(f"{h.outer:>5} {h.objective:>12.6f} {h.grad_norm:>12.4e} "
              f"{h.comm_scalars:>13,} {h.modeled_time_s:>10.4f}")
    print(f"\nfinal objective {result.final_objective():.6f}; "
          f"{result.meter.total_scalars:,} scalars in "
          f"{result.meter.total_rounds:,} rounds")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
