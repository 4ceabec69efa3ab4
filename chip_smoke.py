"""Smoke run of FD-SVRG training and serving on a TPU, at full news20 width.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # the 4-chip mesh path only

The data is the paper's news20 shape (d = 1,355,191, N = 19,954, 455 nnz
per row), generated from ``--seed``; nothing is read from disk.  One chip
runs, in one process, through the entry points a user calls:

* ``train``: ``solve()`` on ``fdsvrg`` with q = 8 feature blocks for 2
  outer iterations of 300 inner steps (the CLI's ``--quick`` shape),
  once on the jnp path and once through the Pallas kernels, each checked
  against ``serial`` (Algorithm 2, plain jnp) at the same seed; then
  ``FDSVRGClassifier(method="fdsvrg", workers=8).fit`` the same way.
* ``serve``: a ``PredictionEngine`` of that classifier answers
  micro-batches of news20 rows through ``MicroBatcher``, with and without
  the kernel; served margins must equal ``decision_function`` on the same
  padded rows and agree with a float64 numpy ``X @ w``.

``--chips 4`` instead runs ``fdsvrg_sharded`` on a 4-device ``("model",)``
mesh against ``fdsvrg`` at q = 4 on the same sample stream, and checks
that the sharded full-gradient step leaves its blocks on 4 devices.

Every phase prints one JSON line (wall and compile seconds, the devices
its arrays live on).  A failed check raises, so the exit code is
non-zero; the last line, printed only when all passed on a TPU, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
These are smoke timings of one cold run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

# eta: the generated full-width rows repeat popular ids, so a row's merged
# squared norm is ~15-22, not 1, and the paper's eta = 2.0 diverges there
# (serial SVRG too); 0.5 descends.
TRAIN = dict(eta=0.5, batch_size=8, inner_steps=300, outer_iters=2)
OBJECTIVE_RTOL = 1e-4  # fdsvrg vs serial: float32 sums in another order
W_RTOL = 1e-3  # max |dw| relative to max |w|, same reason
SERVE_REQUESTS, SERVE_MAX_BATCH = 256, 64


def require_tpu():
    """First act: a TPU or nothing.  Never carries on on the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}"
        )
    return devices


class Phases:
    """Runs phases, timing each and the compilation inside it."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration

    def run(self, name: str, fn, *args, **kw):
        """``fn`` returns (info dict, arrays it produced, value)."""
        c0, t0 = self.compile_s, time.perf_counter()
        info, arrays, value = fn(*args, **kw)
        wall = time.perf_counter() - t0
        devices = sorted(
            {f"{d.platform}:{d.device_kind}:{d.id}"
             for a in arrays for d in a.devices()}
        )
        print(json.dumps({
            "phase": name, "wall_s": wall, "compile_s": self.compile_s - c0,
            "devices": devices, **info,
        }), flush=True)
        return value


def _close(a, b, rtol, what):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = float(np.max(np.abs(a - b)))
    scale = float(np.max(np.abs(b)))
    if not err <= rtol * scale:
        raise AssertionError(f"{what}: max |diff| {err} > {rtol} * {scale}")
    return err


def _check_descent(objectives, what):
    if not all(math.isfinite(o) for o in objectives):
        raise AssertionError(f"{what}: non-finite objective {objectives}")
    path = [math.log(2.0), *objectives]  # f(0) = log 2 for the logistic loss
    if not all(b < a for a, b in zip(path, path[1:])):
        raise AssertionError(f"{what}: objective does not decrease: {path}")


def train_phase(data, seed, method, reference=None, **kw):
    """One solve() at the smoke's training shape; ``kw`` are spec fields
    (q, use_kernels, mesh)."""
    from repro.api import ExperimentSpec, solve

    res = solve(ExperimentSpec(method=method, data=data, seed=seed, **TRAIN,
                               **kw))
    objectives = [h.objective for h in res.history]
    _check_descent(objectives, method)
    info = {"method": method, "objectives": objectives,
            "comm_scalars": res.meter.total_scalars, **{
                k: v for k, v in kw.items() if k in ("q", "use_kernels")}}
    if reference is not None:
        info["max_objective_rel_diff"] = max(
            abs(a - b) / abs(b) for a, b in zip(objectives, reference.objectives())
        )
        if not info["max_objective_rel_diff"] <= OBJECTIVE_RTOL:
            raise AssertionError(f"{method} vs reference objectives: {info}")
        info["max_w_diff"] = _close(res.w, reference.w, W_RTOL, f"{method} w")
    return info, [res.w], res


def estimator_phase(data, seed, jnp_run):
    from repro.api import FDSVRGClassifier

    clf = FDSVRGClassifier(method="fdsvrg", workers=8, seed=seed, **TRAIN)
    clf.fit(data)
    objectives = [h.objective for h in clf.history_]
    _check_descent(objectives, "FDSVRGClassifier")
    # The estimator is a front end to the same solve(): same iterates.
    _close(objectives, jnp_run.objectives(), 1e-6, "estimator objectives")
    _close(clf.coef_, jnp_run.w, 1e-6, "estimator coef_")
    return {"objectives": objectives}, [clf.result_.w], clf


def serve_phase(data, clf):
    import jax.numpy as jnp
    import numpy as np

    from repro.data.sparse import PaddedCSR
    from repro.serve.batching import MicroBatcher
    from repro.serve.engine import PredictionEngine

    indices, values = np.asarray(data.indices), np.asarray(data.values)
    w64 = np.asarray(clf.coef_, np.float64)
    info, arrays = {"requests": SERVE_REQUESTS}, []
    served = {}
    for use_kernels in (False, True):
        engine = PredictionEngine.from_estimator(clf, use_kernels=use_kernels)
        clf.use_kernels = use_kernels  # decision_function's margin path
        batcher = MicroBatcher(max_batch=SERVE_MAX_BATCH)
        for row in range(SERVE_REQUESTS):
            stored = values[row] != 0.0
            batcher.submit(indices[row][stored], values[row][stored])
        batches = batcher.drain()
        out = {}
        for b in batches:
            got = engine.margins(b.indices, b.values)
            arrays.append(engine.snapshot.w)
            want = clf.decision_function(PaddedCSR(
                indices=jnp.asarray(b.indices), values=jnp.asarray(b.values),
                labels=jnp.zeros((b.indices.shape[0],), jnp.float32),
                dim=data.dim,
            ))
            np.testing.assert_array_equal(got, want)
            exact = np.sum(w64[b.indices] * b.values.astype(np.float64), -1)
            np.testing.assert_allclose(got, exact, rtol=1e-5, atol=1e-5)
            for r, req in enumerate(b.requests):
                out[req.req_id] = float(got[r])
        served[use_kernels] = np.array([out[i] for i in range(SERVE_REQUESTS)])
        info[f"batches_kernels={use_kernels}"] = sorted(
            f"{r}x{w}" for r, w in batcher.bucket_counts
        )
    info["max_kernel_vs_jnp_diff"] = _close(
        served[True], served[False], 1e-5, "kernel vs jnp served margins"
    )
    clf.use_kernels = False
    return info, arrays, None


def sharded_placement_phase(data, mesh, result):
    """Run the driver's sharded full-gradient step at the final iterate:
    its feature-sharded output must sit in 4 blocks on 4 devices, and its
    margins must reproduce the run's last reported objective."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.core import losses
    from repro.core.driver import objective_from_margins
    from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, make_fullgrad
    from repro.core.partition import balanced
    from repro.data.block_csr import BlockCSR

    q = mesh.devices.size
    dim = -(-data.dim // q) * q
    padded = dataclasses.replace(data, dim=dim)
    cfg = FDSVRGShardedConfig(
        dim=dim, num_instances=data.num_instances, nnz_max=data.nnz_max,
        eta=TRAIN["eta"], inner_steps=TRAIN["inner_steps"],
        batch_size=TRAIN["batch_size"],
    )
    placed = BlockCSR.from_padded(padded, balanced(dim, q)).on_mesh(
        mesh, ("model",))
    w = jnp.pad(result.w, (0, dim - data.dim))
    z, s0 = make_fullgrad(mesh, cfg, ("model",))(w, placed.groups, placed.labels)
    shards = z.addressable_shards
    starts = sorted(s.index[0].start or 0 for s in shards)
    if len({s.device for s in shards}) != q or starts != [
        i * dim // q for i in range(q)
    ]:
        raise AssertionError(f"z is not one block per device: {shards}")
    objective = objective_from_margins(
        s0, data.labels, result.w, losses.logistic, losses.l2(1e-4)
    )
    _close(objective, result.history[-1].objective, 1e-6, "sharded objective")
    info = {"blocks": q, "block_dim": dim // q,
            "devices_holding_blocks": len({s.device for s in shards}),
            "objective_from_sharded_margins": objective}
    return info, [z, s0], None


def one_chip(phases, data, seed):
    serial = phases.run("train_serial_reference", train_phase, data, seed,
                        "serial")
    fd_jnp = phases.run("train_fdsvrg_jnp", train_phase, data, seed,
                        "fdsvrg", serial, q=8)
    phases.run("train_fdsvrg_kernels", train_phase, data, seed, "fdsvrg",
               serial, q=8, use_kernels=True)
    clf = phases.run("train_estimator", estimator_phase, data, seed, fd_jnp)
    phases.run("serve", serve_phase, data, clf)


def four_chips(phases, data, seed):
    from repro.dist import make_mesh

    mesh = make_mesh((4,), ("model",))
    reference = phases.run("train_fdsvrg_q4_reference", train_phase, data,
                           seed, "fdsvrg", q=4)
    sharded = phases.run("train_fdsvrg_sharded_4chips", train_phase, data,
                         seed, "fdsvrg_sharded", reference, mesh=mesh)
    if len(sharded.w.sharding.device_set) != 4:
        raise AssertionError(f"result not on the 4-device mesh: {sharded.w}")
    phases.run("sharded_placement", sharded_placement_phase, data, mesh,
               sharded)


def load_news20(seed):
    from repro.data import datasets

    data = datasets.load("news20", scaled=False, seed=seed)
    info = {"dim": data.dim, "instances": data.num_instances,
            "nnz_max": data.nnz_max, "seed": seed}
    return info, [data.values], data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the feature-sharded mesh path")
    args = ap.parse_args(argv)

    devices = require_tpu()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    phases = Phases()
    data = phases.run("data", load_news20, args.seed)
    (four_chips if args.chips == 4 else one_chip)(phases, data, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
