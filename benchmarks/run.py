"""Benchmark entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV lines per the harness contract;
full tables land in results/benchmarks/*.csv, and per-suite JSON reports
(including the per-method ``repro.dist`` communication reports) land in
results/benchmarks/BENCH_<name>.json — schema in docs/benchmarks.md.
"""

from __future__ import annotations

import argparse
import time

from benchmarks.common import write_bench_json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small datasets only")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        chaos_bench,
        convergence,
        ingest_bench,
        serve_bench,
        kernels_bench,
        lambda_sensitivity,
        lazy_bench,
        roofline,
        scalability,
        speedup,
    )
    from repro.dist.metering import reports_to_json

    print("name,us_per_call,derived")
    t0 = time.perf_counter()

    def stamp(name, t_start, derived):
        us = (time.perf_counter() - t_start) * 1e6
        print(f"{name},{us:.0f},{derived}", flush=True)
        return us

    t = time.perf_counter()
    _, rows = convergence.run(quick=args.quick)
    us = stamp("fig6_fig7_convergence", t, f"{len(rows)} rows")
    write_bench_json("convergence", {"wall_us": us, "rows": len(rows)})

    t = time.perf_counter()
    _, rows, summary, reports = speedup.run(quick=args.quick)
    fd_vs_ds = [r for r in rows if r[1] == "speedup_vs_dsvrg"]
    us = stamp("tab2_speedup_vs_dsvrg", t,
               ";".join(f"{r[0]}={r[3]}" for r in fd_vs_ds))
    fd_vs_ps = [r for r in rows if r[1] == "speedup_vs_pslite_sgd"]
    print(f"tab3_speedup_vs_pslite,0," + ";".join(f"{r[0]}={r[3]}" for r in fd_vs_ps))
    write_bench_json("speedup", {
        "wall_us": us,
        "modeled_time_to_gap_s": {
            name: {m: t_gap for m, t_gap in times.items()}
            for name, times in summary.items()
        },
        "comm": reports_to_json(reports),
    })

    t = time.perf_counter()
    _, rows = lambda_sensitivity.run()
    us = stamp("fig8_lambda_sensitivity", t, f"{len(rows)} rows")
    write_bench_json("lambda_sensitivity", {"wall_us": us, "rows": len(rows)})

    t = time.perf_counter()
    _, rows, payload = lambda_sensitivity.run_prox(quick=args.quick)
    us = stamp("prox_sparsity_sweep", t,
               f"{len(rows)} rows;comm_parity={payload['comm_parity_with_l2']}")
    payload["wall_us"] = us
    write_bench_json("prox", payload)

    t = time.perf_counter()
    _, rows, times, measured = scalability.run()
    us = stamp("fig9_scalability", t,
               ";".join(f"q{q}={times[1]/times[q]:.2f}x" for q in (1, 4, 8, 16)))
    write_bench_json("scalability", {
        "wall_us": us,
        "modeled_time_s": {str(q): times[q] for q in times},
        "speedup": {str(q): times[1] / times[q] for q in times},
        "comm": reports_to_json({"webspam/fdsvrg": measured}),
    })

    t = time.perf_counter()
    _, rows, blockcsr = kernels_bench.run(quick=args.quick)
    for r in rows:
        print(",".join(map(str, r)))
    us = stamp("kernels_micro_total", t, f"{len(rows)} kernels")
    write_bench_json(
        "kernels", kernels_bench.report_payload(rows, blockcsr, us, args.quick)
    )

    t = time.perf_counter()
    _, rows, lazy_summary = lazy_bench.run(quick=args.quick)
    for r in rows:
        print(",".join(map(str, r)))
    us = stamp(
        "lazy_inner_total", t,
        f"proba {lazy_summary['inner_epoch']['speedup_proba']:.2f}x;"
        f"bitwise={lazy_summary['inner_epoch']['exact_bitwise_equal']};"
        f"comm_parity={lazy_summary['comm']['comm_parity']}",
    )
    write_bench_json(
        "lazy", lazy_bench.report_payload(lazy_summary, us, args.quick)
    )

    t = time.perf_counter()
    _, rows, chaos_summary = chaos_bench.run(quick=args.quick)
    for r in rows:
        print(",".join(map(str, r)))
    us = stamp(
        "chaos_total", t,
        f"{len(chaos_summary['plans'])} plans;"
        f"converged={chaos_summary['all_converged']};"
        f"accounting={chaos_summary['all_accounting_exact']}",
    )
    write_bench_json(
        "chaos", chaos_bench.report_payload(chaos_summary, us, args.quick)
    )

    t = time.perf_counter()
    _, rows, ingest_summary = ingest_bench.run(quick=args.quick)
    for r in rows:
        print(",".join(map(str, r)))
    us = stamp(
        "ingest_total", t,
        f"{ingest_summary['throughput']['streamed_rows_per_s']:.0f}rows/s;"
        f"equal={ingest_summary['streamed_equals_oneshot']};"
        f"warm={ingest_summary['cache']['warm_speedup']:.1f}x",
    )
    write_bench_json(
        "ingest", ingest_bench.report_payload(ingest_summary, us, args.quick)
    )

    t = time.perf_counter()
    _, rows, serve_summary = serve_bench.run(quick=args.quick)
    for r in rows:
        print(",".join(map(str, r)))
    us = stamp(
        "serve_total", t,
        f"{serve_summary['throughput']['predictions_per_s']:.0f}pred/s;"
        f"p99={serve_summary['latency_ms']['p99_ms']:.2f}ms;"
        f"shapes={serve_summary['shapes']['compiled_shapes']};"
        f"bitwise={all(serve_summary['bitwise'].values())}",
    )
    write_bench_json(
        "serve", serve_bench.report_payload(serve_summary, us, args.quick)
    )

    t = time.perf_counter()
    _, rows = roofline.run()
    ok = sum(1 for r in rows if r and r[3] != "FAIL")
    us = stamp("roofline_table", t, f"{ok}/{len(rows)} dryrun combos OK")
    write_bench_json("roofline", {"wall_us": us, "ok": ok, "total": len(rows)})

    print(f"total_benchmark_wall,{(time.perf_counter()-t0)*1e6:.0f},seconds="
          f"{time.perf_counter()-t0:.1f}")


if __name__ == "__main__":
    main()
