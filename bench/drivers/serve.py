"""Serving cells: ``MicroBatcher`` in front of ``PredictionEngine.margins``.

Set-up makes ``w`` on the device and a pool of request rows from the
seed, then scores one padded batch of every (rows, width) bucket the
cell's traffic can flush, which compiles every program the window runs.
One thread then plays the traffic:

* ``"loop": "open"``: requests fall due on a fixed schedule (see
  :mod:`arrivals`), at ``rate`` per second over the window, whatever the
  server is doing; a request is submitted as soon as the loop sees it
  due, and its latency runs from when it was due to when its margin is
  back on the host, so a stall counts against every request behind it;
* ``"loop": "closed"``: ``outstanding`` requests are always in flight;
  each answered one is replaced at once until the window's time is up.

The window closes when the last request has been answered.  Every answer
is then compared with the float64 reference margin of its row.
"""

from __future__ import annotations

import time

import numpy as np

from harness import core, seeds
from harness import lengths as row_lengths

POLL_S = 50e-6  # how often a waiting batcher is asked for deadline flushes
LATE_S = 60.0  # how long past the window an answer is waited for


def lengths(cfg: dict, traffic: dict, count: int, seed: int) -> np.ndarray:
    """Row lengths of the pool (see :mod:`harness.lengths`): the
    traffic's ``lengths``, else the configuration's ``nnz_per_row``."""
    return row_lengths.draw(traffic.get("lengths"), count, seed,
                            fixed=int(cfg["nnz_per_row"]))


def weights(cfg: dict, seed: int):
    """w ~ N(0, w_std^2), float32, made on the device in one call."""
    import jax
    import jax.numpy as jnp

    make = jax.jit(lambda k: jax.random.normal(k, (int(cfg["dim"]),), jnp.float32)
                   * jnp.float32(cfg["w_std"]))
    return make(seeds.jax_key(seed, seeds.WEIGHTS))


def warm(engine, batcher, widths) -> int:
    """Score one zero batch of every (rows, width) bucket."""
    from repro.serve.batching import bucket_width

    shapes = 0
    for width in sorted({bucket_width(int(L), min_width=batcher.min_width)
                         for L in widths}):
        rows = 1
        while rows <= batcher.max_batch:
            engine.margins(np.zeros((rows, width), np.int32),
                           np.zeros((rows, width), np.float32))
            shapes += 1
            rows <<= 1
    return shapes


class Recorder:
    """Per-batch records of one window."""

    def __init__(self, run):
        self.run = run
        self.batches = []  # (req ids, t_flush, t_engine_start, t_done, out)
        self.traced_upto = None  # index of the first batch after the trace
        # longest single engine call, ready() call, submit burst, and gap
        # between two turns of the loop
        self.longest = {"engine": 0.0, "flush": 0.0, "submit": 0.0, "turn": 0.0}

    def note(self, what: str, seconds: float) -> None:
        if seconds > self.longest[what]:
            self.longest[what] = seconds

    def score(self, engine, batch, clock):
        with self.run.span("engine.margins"):
            t_e = clock()
            out = engine.margins(batch.indices, batch.values)
            t_d = clock()
        self.note("engine", t_d - t_e)
        ids = np.fromiter((r.req_id for r in batch.requests), np.int64,
                          len(batch.requests))
        self.batches.append((ids, batch.t_flush, t_e, t_d, out))
        return t_d


def open_loop(run, batcher, engine, pool, rows_of, arrivals, rec):
    ids_p, vals_p, len_p = pool
    n = len(arrivals)
    clock = time.monotonic
    submit, ready = batcher.submit, batcher.ready
    late = np.zeros(n)
    t0 = run.window_opens()
    due = t0 + arrivals
    trace_end = t0 + float(run.traffic["trace_seconds"])
    give_up = t0 + run.seconds + LATE_S
    i = flushed = 0
    next_poll = 0.0
    t_last = t0
    prev = t0
    while flushed < n:
        now = clock()
        rec.note("turn", now - prev)
        prev = now
        if i < n and due[i] <= now:
            with run.span("submit"):
                while i < n and due[i] <= now:
                    r = rows_of[i]
                    k = len_p[r]
                    submit(ids_p[r, :k], vals_p[r, :k], now=due[i])
                    late[i] = now - due[i]
                    i += 1
            rec.note("submit", clock() - now)
            next_poll = 0.0
        if batcher.pending and now >= next_poll:
            t_f = clock()
            with run.span("flush"):
                out = ready(t_f)
            rec.note("flush", clock() - t_f)
            next_poll = now + POLL_S
            for b in out:
                t_last = rec.score(engine, b, clock)
                flushed += b.n_valid
        if run.tracing and now >= trace_end:
            rec.traced_upto = len(rec.batches)
            run.trace_stops()
        if now > give_up:
            break
    if rec.traced_upto is None:
        rec.traced_upto = len(rec.batches)
    run.window_closes(t0, t_last)
    run.samples["due"] = due
    run.samples["lateness_s"] = late[:i]
    run.attempted = n


def closed_loop(run, batcher, engine, pool, rows_of, rec):
    ids_p, vals_p, len_p = pool
    clock = time.monotonic
    submit, ready = batcher.submit, batcher.ready
    outstanding = int(run.traffic["outstanding"])
    due: list[float] = []

    def send(count):
        with run.span("submit"):
            now = clock()
            for _ in range(count):
                r = rows_of[len(due) % len(rows_of)]
                k = len_p[r]
                submit(ids_p[r, :k], vals_p[r, :k], now=now)
                due.append(now)

    t0 = run.window_opens()
    trace_end = t0 + float(run.traffic["trace_seconds"])
    stop = t0 + run.seconds
    give_up = stop + LATE_S
    send(outstanding)
    t_last = prev = t0
    while True:
        now = clock()
        rec.note("turn", now - prev)
        prev = now
        with run.span("flush"):
            out = ready(now)
        rec.note("flush", clock() - now)
        if not out and not batcher.pending:
            break
        for b in out:
            t_last = rec.score(engine, b, clock)
            if t_last < stop:
                send(b.n_valid)
        if run.tracing and now >= trace_end:
            rec.traced_upto = len(rec.batches)
            run.trace_stops()
        if now > give_up:
            break
    if rec.traced_upto is None:
        rec.traced_upto = len(rec.batches)
    run.window_closes(t0, t_last)
    run.samples["due"] = np.asarray(due)
    run.attempted = len(due)


def run(run) -> None:
    from repro.serve.batching import MicroBatcher
    from repro.serve.engine import PredictionEngine, WeightSnapshot

    cfg, tr = run.config, run.traffic
    w = weights(cfg, run.seed)
    engine = PredictionEngine(WeightSnapshot.from_dense(w, 0))
    pool_n = int(tr["pool"])
    lens = lengths(cfg, tr, pool_n, run.seed)
    ids, vals = run.cell.rows.rows(cfg, lens, run.seed, 2)
    pool = (np.asarray(ids), np.asarray(vals), lens)
    del ids, vals
    batcher = MicroBatcher(**tr["batcher"])
    warm(engine, batcher, lens)
    order = seeds.rng(run.seed, seeds.ORDER)
    rec = Recorder(run)
    if tr["loop"] == "open":
        arrivals = core.load_module(run.cell.bench / "arrivals.py").schedule(
            tr, run.seconds, seeds.rng(run.seed, seeds.ARRIVALS))
        reps = -(-len(arrivals) // pool_n)
        rows_of = np.concatenate([order.permutation(pool_n) for _ in range(reps)])
        open_loop(run, batcher, engine, pool, rows_of, arrivals, rec)
    elif tr["loop"] == "closed":
        rows_of = order.permutation(pool_n)
        closed_loop(run, batcher, engine, pool, rows_of, rec)
    else:
        raise ValueError(f"unknown loop {tr['loop']!r}")
    del engine
    settle(run, rec, lens, rows_of[np.arange(run.attempted) % len(rows_of)])
    w = np.asarray(w)
    compare(run, pool, w, cfg["limits"]["serve"])
    run.samples.update(pool=pool, w=w)


def controls(run) -> dict:
    """Readings of the control: every answer of the run replaced by the
    reference margin of its row computed in bfloat16."""
    ids_p, vals_p, _ = run.samples["pool"]
    got = run.cell.reference.margins_in("bfloat16", ids_p, vals_p, run.samples["w"])
    rows = run.samples["rows_of"]
    return {"bfloat16": gaps(got[rows], rows, run.samples["pool"],
                             run.samples["w"], run.cell.reference)}


def settle(run, rec, lens, rows_of):
    """Per-request records from the per-batch ones; ``rows_of[j]`` is
    the pool row request j carried."""
    n = run.attempted
    due = run.samples["due"]
    done = np.full(n, np.nan)
    flush = np.full(n, np.nan)
    got = np.full(n, np.nan)
    traced = np.zeros(n, bool)
    engine_s = []
    for b, (ids, t_f, t_e, t_d, out) in enumerate(rec.batches):
        out = np.asarray(out, np.float64).reshape(-1)
        k = min(len(out), len(ids))
        done[ids[:k]] = t_d
        got[ids[:k]] = out[:k]
        flush[ids] = t_f
        engine_s.append(t_d - t_e)
        traced[ids] = b < rec.traced_upto
    answered = np.isfinite(got)
    flushed = np.isfinite(flush)
    run.failed = int(n - np.sum(answered))
    run.samples.update(
        latency_s=(done - due)[answered],
        queue_s=(flush - due)[flushed & traced],
        engine_s=np.asarray(engine_s[:rec.traced_upto]),
        margins=got, rows_of=rows_of)
    run.counts.update({f"longest_{k}_ms": 1e3 * v for k, v in rec.longest.items()})
    run.counts.update(
        completed=int(np.sum(answered)),
        stored_ids=int(np.sum(lens[rows_of][answered])),
        batches=len(rec.batches), traced_batches=rec.traced_upto,
        traced_requests=int(np.sum(traced & answered)),
        traced_ids=int(np.sum(lens[rows_of][traced & answered])))
    late = run.samples.get("lateness_s")
    if late is not None and len(late):
        run.counts["lateness_p99_ms"] = float(np.quantile(late, 0.99) * 1e3)
        run.counts["lateness_max_ms"] = float(np.max(late) * 1e3)


def gaps(got, rows_of, pool, w, ref) -> dict:
    """The widest gap of an answer from its row's float64 margin, over
    the row's sum of |w_k v_k|."""
    ids_p, vals_p, _ = pool
    want = ref.margins(ids_p, vals_p, w)
    scale = np.maximum(ref.magnitudes(ids_p, vals_p, w), 1e-30)
    answered = np.isfinite(got)
    rows = rows_of[answered]
    gap = np.abs(got[answered] - want[rows]) / scale[rows]
    return {"margin_gap": float(np.max(gap)) if gap.size else float("inf")}


def compare(run, pool, w, limits) -> None:
    for name, value in gaps(run.samples["margins"], run.samples["rows_of"],
                            pool, w, run.cell.reference).items():
        run.check(name, value if np.isfinite(value) else 1e300, limits[name])
