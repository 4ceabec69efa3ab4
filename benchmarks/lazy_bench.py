"""Lazy O(nnz) inner-step benchmark: delayed-decay epochs vs the dense
fused update on an ultra-sparse preset.

The paper's inner step densifies every block of ``w`` once per sampled
row (the ``fused_update`` / ``prox_update`` path): O(d/q) work per step
regardless of sparsity.  The lazy kernels (PR 6) touch only the features
present in the current row — O(u * nnz) per step plus one O(d) epoch-end
flush — so on text-like data (nnz/d <= 1e-3) a whole inner epoch drops
from O(M * d) to O(M * u * nnz + d).  This bench measures that ratio on
one jitted inner epoch and certifies the two invariants the drivers rely
on:

* **bitwise**: the exact-lazy epoch equals the dense epoch bit-for-bit
  on the measured preset (q=1 — the serial contract; see
  tests/test_lazy_updates.py for the full q-matrix story);
* **comm parity**: lazy is a compute-layout change only — ``run_fdsvrg``
  meters the same scalars/rounds and the same analytic schedule with the
  flag on or off.

Standalone entry point with a ``--quick`` smoke mode for CI:

    PYTHONPATH=src python -m benchmarks.lazy_bench [--quick]

writes results/benchmarks/lazy_inner.csv and BENCH_lazy.json.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import measure_us, write_bench_json, write_csv
from repro.core import fdsvrg, losses
from repro.core.fdsvrg import SVRGConfig, run_fdsvrg
from repro.core.partition import balanced
from repro.data.block_csr import BlockCSR
from repro.data.synthetic import make_sparse_classification


def _timeit(fn, iters=7) -> dict:
    """Median-over-repeats with a spread field (benchmarks.common
    .measure_us): epoch timings on a shared box are noisy (50%
    run-to-run swings observed), so the payload carries the noise
    estimate instead of hiding it."""
    return measure_us(lambda: jax.block_until_ready(fn()), repeats=iters)


def _epoch_inputs(quick: bool):
    """Ultra-sparse preset: nnz/d <= 1e-3, the regime the lazy trick
    targets (text shards; news20/url-like column sparsity)."""
    if quick:
        d, n, nnz, m_steps, u = 8192, 256, 8, 128, 4
    else:
        d, n, nnz, m_steps, u = 32768, 1024, 16, 768, 8
    data = make_sparse_classification(
        dim=d, num_instances=n, nnz_per_instance=nnz, seed=0
    )
    block_data = BlockCSR.from_padded(data, balanced(d, 1))
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.01)
    samples = jnp.asarray(
        rng.integers(0, n, size=(m_steps, u)).astype(np.int32)
    )
    mask = jnp.ones(m_steps, dtype=jnp.float32)
    shape = {"d": d, "N": n, "nnz": nnz, "M": m_steps, "u": u,
             "nnz_over_d": nnz / d}
    return data, block_data, w0, samples, mask, shape


def bench_inner_epoch(quick: bool) -> tuple[list[list], dict]:
    data, bd, w0, samples, mask, shape = _epoch_inputs(quick)
    eta = 0.1
    rows: list[list] = []
    summary: dict = {"shape": shape, "regs": {}}

    for rname, reg in (("l2", losses.l2(1e-4)), ("l1", losses.l1(1e-4))):
        z, s0 = fdsvrg._full_grad_blocks(
            bd.groups, data.labels, w0, "logistic",
            bd.block_dims, False,
        )
        corr = fdsvrg._lazy_corrections(
            bd, data.num_instances, samples.shape[1], "proba"
        )

        def dense():
            return fdsvrg._inner_epoch(
                bd.indices, bd.values, data.labels, w0, z, s0, samples,
                eta, mask, "logistic", reg.name, reg.lam, bd.block_dims,
                False, lam2=reg.lam2,
            )

        def lazy_exact():
            return fdsvrg._lazy_inner_epoch(
                bd.indices, bd.values, data.labels, w0, z, s0, samples,
                eta, mask, None, "logistic", reg.name, reg.lam,
                bd.block_dims, False, "exact", lam2=reg.lam2,
            )

        def lazy_proba():
            return fdsvrg._lazy_inner_epoch(
                bd.indices, bd.values, data.labels, w0, z, s0, samples,
                eta, mask, corr, "logistic", reg.name, reg.lam,
                bd.block_dims, False, "proba", lam2=reg.lam2,
            )

        # the contract the speedup is allowed to claim: same bits out
        a = np.asarray(dense())
        b = np.asarray(lazy_exact())
        bitwise = bool((a.view(np.uint32) == b.view(np.uint32)).all())

        m_dense = _timeit(dense)
        m_exact = _timeit(lazy_exact)
        m_proba = _timeit(lazy_proba)
        t_dense, t_exact, t_proba = m_dense["us"], m_exact["us"], m_proba["us"]
        rows += [
            [f"inner_epoch_dense_{rname}", f"{t_dense:.1f}",
             f"[M={shape['M']},d={shape['d']}] "
             f"spread={m_dense['spread']:.2f}"],
            [f"inner_epoch_lazy_exact_{rname}", f"{t_exact:.1f}",
             f"{t_dense / t_exact:.2f}x vs dense, bitwise={bitwise}, "
             f"spread={m_exact['spread']:.2f}"],
            [f"inner_epoch_lazy_proba_{rname}", f"{t_proba:.1f}",
             f"{t_dense / t_proba:.2f}x vs dense, "
             f"spread={m_proba['spread']:.2f}"],
        ]
        summary["regs"][rname] = {
            "dense_us": t_dense,
            "lazy_exact_us": t_exact,
            "lazy_proba_us": t_proba,
            "dense_spread": m_dense["spread"],
            "lazy_exact_spread": m_exact["spread"],
            "lazy_proba_spread": m_proba["spread"],
            "timing_repeats": m_dense["repeats"],
            "speedup_exact": t_dense / t_exact,
            "speedup_proba": t_dense / t_proba,
            "exact_bitwise_equal": bitwise,
        }

    summary["speedup_exact"] = min(
        r["speedup_exact"] for r in summary["regs"].values()
    )
    summary["speedup_proba"] = min(
        r["speedup_proba"] for r in summary["regs"].values()
    )
    summary["exact_bitwise_equal"] = all(
        r["exact_bitwise_equal"] for r in summary["regs"].values()
    )
    summary["spread"] = max(
        max(r["dense_spread"], r["lazy_exact_spread"], r["lazy_proba_spread"])
        for r in summary["regs"].values()
    )
    return rows, summary


def bench_comm_parity(quick: bool) -> dict:
    """Lazy is a per-worker compute change: the metered communication and
    the analytic cost-model schedule must not move at all."""
    d, n, nnz = (2048, 128, 6) if quick else (8192, 512, 8)
    data = make_sparse_classification(
        dim=d, num_instances=n, nnz_per_instance=nnz, seed=1
    )
    part = balanced(d, 4)
    cfg = SVRGConfig(eta=0.5, inner_steps=40, outer_iters=2, seed=3,
                     option="I")
    reg = losses.l1(1e-4)
    eager = run_fdsvrg(data, part, losses.logistic, reg, cfg)
    lazy = run_fdsvrg(data, part, losses.logistic, reg, cfg,
                      lazy_updates="exact")
    parity = (
        eager.meter.total_scalars == lazy.meter.total_scalars
        and eager.meter.total_rounds == lazy.meter.total_rounds
        and all(
            a.comm_scalars == b.comm_scalars
            and a.modeled_time_s == b.modeled_time_s
            for a, b in zip(eager.history, lazy.history)
        )
    )
    return {
        "q": part.num_blocks,
        "total_scalars": eager.meter.total_scalars,
        "total_rounds": eager.meter.total_rounds,
        "comm_parity": bool(parity),
    }


def run(quick: bool = False):
    rows, inner = bench_inner_epoch(quick)
    parity = bench_comm_parity(quick)
    path = write_csv("lazy_inner.csv", ["name", "us_per_call", "derived"], rows)
    return path, rows, {"inner_epoch": inner, "comm": parity}


def report_payload(summary: dict, wall_us: float, quick: bool) -> dict:
    """The BENCH_lazy.json schema — one builder for the standalone and
    the aggregate (benchmarks.run) entry points."""
    return {
        "wall_us": wall_us,
        "quick": quick,
        "timing": {"estimator": "median", "spread": "(max-min)/median"},
        "speedup_exact": summary["inner_epoch"]["speedup_exact"],
        "speedup_proba": summary["inner_epoch"]["speedup_proba"],
        "spread": summary["inner_epoch"]["spread"],
        "exact_bitwise_equal": summary["inner_epoch"]["exact_bitwise_equal"],
        "comm_parity": summary["comm"]["comm_parity"],
        "detail": summary,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI smoke mode)")
    args = ap.parse_args()
    t0 = time.perf_counter()
    path, rows, summary = run(quick=args.quick)
    payload = report_payload(
        summary, (time.perf_counter() - t0) * 1e6, args.quick)
    write_bench_json("lazy", payload)
    print(f"lazy: wrote {len(rows)} rows to {path}")
    for r in rows:
        print("  ", ",".join(map(str, r)))
    print(
        f"  lazy inner epoch: exact {payload['speedup_exact']:.2f}x / "
        f"proba {payload['speedup_proba']:.2f}x vs the dense fused update "
        f"at nnz/d={summary['inner_epoch']['shape']['nnz_over_d']:.1e} "
        f"(bitwise={payload['exact_bitwise_equal']}, "
        f"comm parity={payload['comm_parity']})"
    )


if __name__ == "__main__":
    main()
