"""Training cells: ``repro.api.solve`` through the user's front door.

Every call the run makes, in set-up and in the window, is the window's
own: ``solve`` for ``outers_per_call`` outer iterations, warm-started
through ``init_w`` from the last call's iterate, with a seed of its own.
Set-up makes the data set on the device and drives the first
``check_calls`` calls from w = 0, which compiles every program the
window runs; their objectives, first gradient norm and final iterate are
kept.  The window continues from that iterate, call after call, until
``--seconds`` have passed; it closes when the call running then returns.
After it, the kept readings are compared with the float64 reference
driven through the same calls.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from harness import seeds, work


def shapes(cfg: dict, traffic: dict) -> dict:
    n, u = int(cfg["num_instances"]), int(traffic["batch_size"])
    m = n // u if traffic["inner_steps"] == "paper" else int(traffic["inner_steps"])
    return {"dim": int(cfg["dim"]), "n": n,
            "nnz_total": n * int(cfg["nnz_per_row"]), "u": u, "m": m}


def make_spec(run, data, **kw):
    from repro.api import ExperimentSpec
    from repro.core import losses

    cfg, tr = run.config, run.traffic
    if cfg["reg"] != "l2" or cfg["loss"] != "logistic":
        raise ValueError("the training driver runs logistic + l2 configurations")
    return ExperimentSpec(
        method=tr["method"], q=int(tr["q"]), data=data, loss=cfg["loss"],
        reg=losses.l2(float(cfg["lam"])), eta=float(cfg["eta"]),
        batch_size=int(tr["batch_size"]), inner_steps=tr["inner_steps"],
        option=tr["option"], **kw)


def solve(spec):
    """The timed entry; a module attribute so that tests can break it."""
    from repro.api import solve as api_solve

    return api_solve(spec)


def checked_calls(run) -> list[tuple[int, int]]:
    """``(seed, outers)`` of the calls set-up makes and the reference
    follows."""
    per_call = int(run.traffic["outers_per_call"])
    return [(seeds.int32(run.seed, seeds.SOLVE, k), per_call)
            for k in range(int(run.traffic["check_calls"]))]


def run(run) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data.sparse import PaddedCSR

    cfg, tr = run.config, run.traffic
    ids, vals, labels = run.cell.rows.dataset(cfg, run.seed)
    data = PaddedCSR(indices=ids, values=vals, labels=labels, dim=int(cfg["dim"]))
    sh = shapes(cfg, tr)
    checked = checked_calls(run)
    w = jnp.zeros((sh["dim"],), vals.dtype)
    history = []
    for seed, outers in checked:
        res = solve(make_spec(run, data, outer_iters=outers, init_w=w, seed=seed))
        w = jax.block_until_ready(res.w)
        history += res.history
    prog = {"objectives": [h.objective for h in history],
            "grad_norms": [h.grad_norm for h in history],
            "w": np.asarray(w, np.float64)}
    run.attempted = len(checked)

    per_call = int(tr["outers_per_call"])
    outers = calls = 0
    t_open = run.window_opens()
    trace_end = t_open + float(tr["trace_seconds"])
    while time.monotonic() < t_open + run.seconds:
        calls += 1
        with run.span("solve"):
            res = solve(make_spec(run, data, outer_iters=per_call, init_w=w,
                                  seed=seeds.int32(run.seed, seeds.SOLVE,
                                                   len(checked) + calls)))
            w = jax.block_until_ready(res.w)
        outers += len(res.history)
        if run.tracing and time.monotonic() >= trace_end:
            run.counts["traced_outers"] = outers
            run.trace_stops()
    if run.tracing:
        run.counts["traced_outers"] = outers
    run.window_closes(t_open)
    run.attempted += calls
    run.counts.update(outers=outers, calls=calls,
                      rows_visited=outers * work.svrg_outer_rows(
                          n=sh["n"], u=sh["u"], m=sh["m"]),
                      outer_bytes=work.svrg_outer_bytes(**sh),
                      outer_flops=work.svrg_outer_flops(**sh),
                      inner_steps=sh["m"])
    host = (np.asarray(ids), np.asarray(vals), np.asarray(labels))
    del data, res, w, ids, vals, labels
    ref = reference(run, host)
    print(f"train: objectives {prog['objectives']} reference {ref['objectives']}",
          file=sys.stderr)
    compare(run, prog, ref, cfg["limits"]["train"])
    run.samples.update(host=host, ref=ref)


def controls(run) -> dict:
    """Readings of the control and of each fault a training cell can
    have, against the same float64 reference, on the run's own data and
    calls: the reference in bfloat16 in the program's place, and in
    float32 with half of each mini-batch left out, or with each call's
    warm start ignored.  (A state left unchanged reads change_gap = 1 by
    definition.)"""
    sh = shapes(run.config, run.traffic)
    ids, vals, labels = run.samples["host"]
    out = {}
    for name, dtype, fault in (("bfloat16", "bfloat16", None),
                               ("half_batch", "float32", "half_batch"),
                               ("cold_start", "float32", "cold_start"),
                               ("float32", "float32", None)):
        got = run.cell.reference.svrg_in(
            dtype, ids, vals, labels, dim=sh["dim"], lam=float(run.config["lam"]),
            eta=float(run.config["eta"]), u=sh["u"], m=sh["m"],
            calls=checked_calls(run), fault=fault)
        out[name] = gaps(got, run.samples["ref"])
    return out


def reference(run, host) -> dict:
    cfg, sh = run.config, shapes(run.config, run.traffic)
    ids, vals, labels = host
    return run.cell.reference.svrg(
        ids, vals, labels, dim=sh["dim"], lam=float(cfg["lam"]),
        eta=float(cfg["eta"]), u=sh["u"], m=sh["m"], calls=checked_calls(run))


def gaps(prog: dict, ref: dict) -> dict:
    """Relative gaps: the worst outer's objective, the gradient norm
    after the first outer, and the norm of the change from w = 0 over
    every checked outer."""
    po, ro = np.asarray(prog["objectives"]), np.asarray(ref["objectives"])
    if po.shape != ro.shape or not np.all(np.isfinite(po)):
        return {"loss_gap": float("inf"), "grad_gap": float("inf"),
                "change_gap": float("inf")}
    rg = ref["grad_norms"][0]
    rn = float(np.linalg.norm(ref["w"]))
    return {
        "loss_gap": float(np.max(np.abs(po - ro) / np.abs(ro))),
        "grad_gap": abs(prog["grad_norms"][0] - rg) / rg,
        "change_gap": abs(float(np.linalg.norm(prog["w"])) - rn) / rn,
    }


def compare(run, prog: dict, ref: dict, limits: dict) -> None:
    for name, value in gaps(prog, ref).items():
        run.check(name, value if np.isfinite(value) else 1e300, limits[name])
