"""Training cells on a mesh: ``repro.api.solve(fdsvrg_sharded)`` over
feature blocks made one per chip.

As ``train.py``, with the data made where it is used: the cell's rows
module makes block l of every row on the chip that holds shard l of the
mesh (``rows.blocks``), the program takes the blocks as they sit
(``BlockCSR.from_blocks``) and every call of the run, in set-up and in
the window, is ``solve`` on that layout for ``outers_per_call`` outer
iterations, warm-started through ``init_w``.  A program without
``BlockCSR.from_blocks`` cannot take such data; the run then stops
before making any.  Once the blocks are made, each block's stored
entries (and no padding) come to the host, and the float64 reference
(``refs/linear_fd.py``) follows the checked calls on the host while the
rest of set-up and the window run on the chips: it takes about two
minutes, and the chips would otherwise wait for it at the end.  Its
time goes mostly to compiled code that releases the interpreter lock,
which the window's loop, waiting on the device, hardly needs.
"""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import numpy as np

from harness import core, work_fd

train = core.load_module(Path(__file__).resolve().parent / "train.py")

AXES = ("model",)
# Rows a block moves to the host at once, at most.
HOST_ROWS = 16384


def require_blocks_entry() -> None:
    """Exit non-zero unless the program takes blocks made one per chip."""
    from repro.data.block_csr import BlockCSR

    if not hasattr(BlockCSR, "from_blocks"):
        sys.exit("bench: the program has no BlockCSR.from_blocks; it cannot "
                 "take feature blocks made one per chip")


def shapes(cfg: dict, traffic: dict) -> dict:
    return dict(train.shapes(cfg, traffic), q=int(traffic["q"]))


def _compact_fn():
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("rows", "cap"))
    def compact(ids, vals, counts, start, *, rows, cap):
        """The stored entries of ``rows`` rows from ``start`` (each row's
        are its first lanes), packed into ``cap`` slots by a gather."""
        i = jax.lax.dynamic_slice_in_dim(ids, start, rows)
        v = jax.lax.dynamic_slice_in_dim(vals, start, rows)
        counts = jax.lax.dynamic_slice_in_dim(counts, start, rows)
        row = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), counts,
                         total_repeat_length=cap)
        first = jnp.cumsum(counts) - counts
        lane = jnp.minimum(jnp.arange(cap, dtype=jnp.int32) - first[row],
                           i.shape[1] - 1)
        at = row * i.shape[1] + lane
        return i.reshape(-1)[at], v.reshape(-1)[at]

    return compact


def host_blocks(blocks, shares, bounds) -> list[tuple]:
    """Per block ``(indptr, ids, vals, lo, hi)`` on the host: only the
    stored entries cross, packed on the device a chunk of rows at a
    time."""
    import jax

    compact = _compact_fn()
    n = int(shares.shape[0])
    rows = max(r for r in range(1, min(n, HOST_ROWS) + 1) if n % r == 0)
    out = []
    for l, (ids, vals) in enumerate(blocks):
        counts = shares[:, l].astype(np.int64)
        per_chunk = counts.reshape(-1, rows).sum(axis=1)
        cap = int(per_chunk.max())
        dev_counts = jax.device_put(shares[:, l].astype(np.int32), ids.sharding)
        parts_i, parts_v = [], []
        for c, total in enumerate(per_chunk):
            start = c * rows
            ci, cv = compact(ids, vals, dev_counts, start, rows=rows, cap=cap)
            parts_i.append(np.asarray(ci)[:total])
            parts_v.append(np.asarray(cv)[:total])
        indptr = np.concatenate([[0], np.cumsum(counts)])
        out.append((indptr, np.concatenate(parts_i), np.concatenate(parts_v),
                    bounds[l], bounds[l + 1]))
    return out


def run(run) -> None:
    require_blocks_entry()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.fdsvrg_shardmap import mesh_partition
    from repro.data.block_csr import BlockCSR
    from repro.dist import make_mesh

    cfg, tr = run.config, run.traffic
    sh = shapes(cfg, tr)
    q, dim, n = sh["q"], sh["dim"], sh["n"]
    mesh = make_mesh((q,), AXES, devices=run.devices[:q])
    rows_mod = run.cell.rows
    t = time.monotonic()
    blocks, labels, _, shares = rows_mod.blocks(cfg, run.seed, mesh, AXES)
    print(f"train_mesh: blocks made in {time.monotonic() - t:.1f} s", file=sys.stderr)
    partition = mesh_partition(dim, q)
    bounds = rows_mod.bounds(dim, q)
    if list(partition.bounds) != bounds:
        raise RuntimeError(f"the program's blocks {partition.bounds} are not "
                           f"the data's {bounds}")
    t = time.monotonic()
    host = host_blocks(blocks, shares, bounds)
    host_labels = np.asarray(labels)
    print(f"train_mesh: stored entries to host {time.monotonic() - t:.1f} s",
          file=sys.stderr)
    ref_box: dict = {}

    def follow() -> None:
        t = time.monotonic()
        try:
            ref_box["ref"] = reference(run, host, host_labels)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            ref_box["error"] = e
        ref_box["seconds"] = time.monotonic() - t

    follower = threading.Thread(target=follow, name="reference", daemon=True)
    follower.start()

    t = time.monotonic()
    data = BlockCSR.from_blocks([i for i, _ in blocks], [v for _, v in blocks],
                                partition, labels, dim)
    print(f"train_mesh: layout built in {time.monotonic() - t:.1f} s", file=sys.stderr)
    checked = train.checked_calls(run)
    t = time.monotonic()
    w = jnp.zeros((dim,), jnp.float32)
    history = []
    for seed, outers in checked:
        res = train.solve(train.make_spec(run, data, outer_iters=outers,
                                          init_w=w, seed=seed, mesh=mesh))
        w = jax.block_until_ready(res.w)
        history += res.history
    prog = {"objectives": [h.objective for h in history],
            "grad_norms": [h.grad_norm for h in history],
            "w": np.asarray(w, np.float64)}
    run.attempted = len(checked)
    print(f"train_mesh: checked calls {time.monotonic() - t:.1f} s", file=sys.stderr)
    per_call = int(tr["outers_per_call"])
    outers = calls = 0
    t_open = run.window_opens()
    trace_end = t_open + float(tr["trace_seconds"])
    while time.monotonic() < t_open + run.seconds:
        calls += 1
        with run.span("solve"):
            res = train.solve(train.make_spec(
                run, data, outer_iters=per_call, init_w=w, mesh=mesh,
                seed=train.seeds.int32(run.seed, train.seeds.SOLVE,
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
    work = dict(dim=dim, n=n, nnz_total=sh["nnz_total"], q=q)
    run.counts.update(
        outers=outers, calls=calls,
        rows_visited=outers * train.work.svrg_outer_rows(n=n, u=sh["u"], m=sh["m"]),
        outer_bytes=work_fd.outer_bytes(u=sh["u"], m=sh["m"], **work),
        outer_flops=work_fd.outer_flops(u=sh["u"], m=sh["m"], **work),
        full_grad_bytes=work_fd.full_grad_bytes(**work),
        inner_steps=sh["m"], batch_size=sh["u"])
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in run.devices[:q]]
    print(f"train_mesh: peak bytes per chip {peaks}", file=sys.stderr)
    if run.trace is not None:
        print(f"train_mesh: modules {sorted(run.trace.modules)}", file=sys.stderr)
        reduce_ops = sorted(k for k in run.trace.ops if "all-reduce" in k)
        print(f"train_mesh: all-reduce ops {reduce_ops}", file=sys.stderr)
    del data, res, w, host
    t = time.monotonic()
    follower.join()
    if "error" in ref_box:
        raise ref_box["error"]
    ref = ref_box["ref"]
    print(f"train_mesh: reference {ref_box['seconds']:.1f} s, "
          f"{time.monotonic() - t:.1f} s of it after the window", file=sys.stderr)
    print(f"train: objectives {prog['objectives']} reference {ref['objectives']}",
          file=sys.stderr)
    train.compare(run, prog, ref, cfg["limits"]["train"])
    rows_sharding = NamedSharding(mesh, P(AXES, None))
    stack = [jax.make_array_from_single_device_arrays(
        (q * n, blocks[0][k].shape[1]), rows_sharding, [b[k] for b in blocks])
        for k in (0, 1)]
    run.samples.update(ref=ref, mesh=(mesh, stack[0], stack[1], labels))


def reference(run, host, labels) -> dict:
    cfg, sh = run.config, shapes(run.config, run.traffic)
    return run.cell.reference.svrg(
        host, labels, dim=sh["dim"], lam=float(cfg["lam"]), eta=float(cfg["eta"]),
        u=sh["u"], m=sh["m"], calls=train.checked_calls(run))


def controls(run) -> dict:
    """Readings of the control and of the half-batch fault against the
    same float64 reference, on the run's own data and calls, computed on
    the run's mesh: the reference in bfloat16 in the program's place,
    and in float32 with half of each mini-batch left out.  (One checked
    call starts from w = 0, so a warm start ignored cannot show here; a
    state left unchanged reads change_gap = 1 by definition.)"""
    sh = shapes(run.config, run.traffic)
    mesh, ids, vals, labels = run.samples["mesh"]
    out = {}
    for name, dtype, fault in (("bfloat16", "bfloat16", None),
                               ("half_batch", "float32", "half_batch")):
        got = run.cell.reference.svrg_in(
            dtype, ids, vals, labels, mesh=mesh, axes=AXES, dim=sh["dim"],
            lam=float(run.config["lam"]), eta=float(run.config["eta"]),
            u=sh["u"], m=sh["m"], calls=train.checked_calls(run), fault=fault)
        out[name] = train.gaps(got, run.samples["ref"])
    return out
