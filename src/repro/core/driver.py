"""The ONE outer-loop harness every optimizer driver runs on.

Before this module existed, the outer loop — snapshot rotation, sample
drawing, objective/optimality reporting, history construction — was
hand-copied into six drivers, and the copies drifted (PR 3 fixed the same
stale grad-norm bug six times; the shard_map driver then drifted again).
:func:`run_outer_loop` is the single engine; a driver supplies three
hooks and nothing else:

``snapshot(w) -> (z_data, s0)``
    The data part of the full gradient and the margins at ``w``,
    **compute only** — never meters.  The harness calls it once after
    every epoch: the post-epoch full gradient doubles as the next
    outer's snapshot AND as the same-iterate diagnostic pair for
    reporting.  Before the first epoch it needs the pair at ``init_w``:
    it takes the caller's ``init_snapshot`` (the :attr:`RunResult.final`
    of a run that ended at ``init_w``) or a checkpoint's, and only
    otherwise calls ``snapshot(init_w)`` — the one full gradient a run
    pays beyond its outers.

``epoch(t, rng, w, z_data, s0) -> w``
    One outer iteration's inner work: draw samples (via
    :func:`draw_samples` / :func:`option_mask` so every driver consumes
    the rng stream the same way), run the inner loop, and meter/charge
    ALL the traffic and modeled compute this outer consumes — including
    the snapshot tree it consumed — through the backend, with the closed
    forms of :mod:`repro.dist.costs`.  Metering lives here, not in
    ``snapshot``, so the per-run meter reflects the algorithm (one
    full-gradient phase per outer), not the reporting overhead.

``evaluate(w, z_data, s0) -> (objective, optimality_norm)``
    Defaults to :func:`make_same_iterate_eval`: f(w) from the margins
    already in hand plus the optimality residual pairing z and w at the
    SAME iterate (gradient norm for smooth g, prox gradient-mapping norm
    otherwise).

The harness owns the rng construction, wall-clock timing, and the
:class:`RunResult`/:class:`OuterRecord` history schema, so every method —
serial, FD-SVRG (metered sim, worker simulation, shard_map), DSVRG, and
the parameter-server baselines — reports identically and a new scenario
is a one-place change.

It also owns the failure semantics, because SVRG hands them to us: the
replicated snapshot (w̃, z, s0) held at the top of each outer iteration
is a complete, consistent recovery point, so both recovery paths are
*epoch-abort-to-snapshot* — throw away the failed epoch and rerun it
from state every worker already holds:

* a **divergence guard** (:class:`RecoveryPolicy`): a non-finite or
  exploding objective after an epoch (e.g. a corrupted collective
  payload, or an eta too large for the spectrum) aborts the epoch,
  scales eta down by ``eta_backoff``, and reruns from the snapshot;
* **unrecoverable faults** (any :class:`repro.dist.FaultError`, e.g. a
  worker crash or retries exhausted) abort the epoch the same way, with
  the abort path's extra communication metered via the policy's
  ``on_abort`` hook (the FD drivers default it to one full-gradient
  redistribution).

and **checkpoint/resume** (:class:`CheckpointPolicy`): every k outers
the harness persists (w, snapshot, rng state, meter counters, modeled
time, history) through :mod:`repro.checkpoint.ckpt`; a resumed run is
bit-identical to the uninterrupted one — iterates, objectives, meter
counters, and modeled time exactly equal (pinned in
``tests/test_faults.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import ckpt
from repro.core import losses as losses_lib
from repro.dist import Collectives, CommMeter, FaultError


class DivergenceError(FaultError):
    """The post-epoch iterate is numerically broken (NaN/inf objective or
    exploding optimality norm) — raised by the harness's divergence guard
    and recovered like any other fault: abort to snapshot (plus eta
    backoff, since divergence is usually a step-size problem)."""


@dataclasses.dataclass
class OuterRecord:
    outer: int
    objective: float
    grad_norm: float
    comm_scalars: int
    comm_rounds: int
    modeled_time_s: float
    wall_time_s: float


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """The data part of the full gradient ``z`` and the margins ``s0`` at
    the iterate ``w``, in the driver's own layout (on a mesh: ``w`` and
    ``z`` padded and feature-sharded).  What the outer loop holds between
    epochs, and all a later run needs to start at ``w`` without
    computing the full gradient there again."""

    w: jax.Array
    z: jax.Array
    s0: jax.Array


@dataclasses.dataclass
class RunResult:
    w: jax.Array
    history: list[OuterRecord]
    meter: CommMeter
    # The pair the last rotation produced, at the final iterate: a run
    # warm-started from it passes it back as ``init_snapshot``.
    final: Snapshot | None = None

    def objectives(self) -> np.ndarray:
        return np.array([h.objective for h in self.history])

    def final_objective(self) -> float:
        return self.history[-1].objective


# ---------------------------------------------------------------------------
# Same-iterate reporting (objective from cached margins, optimality residual)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("loss_name", "reg_name"))
def _objective_from_margins_impl(s, labels, w, lam, lam2, loss_name, reg_name):
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    return jnp.mean(loss.value(s, labels)) + reg.value(w)


def objective_from_margins(
    s: jax.Array,
    labels: jax.Array,
    w: jax.Array,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
) -> float:
    """Objective at ``w`` given the margins ``s = w^T x_i`` already in hand
    (the snapshot computes them anyway — no point paying a second
    O(N·nnz) sweep just to report f(w))."""
    return float(
        _objective_from_margins_impl(
            s, labels, w, reg.lam, reg.lam2, loss.name, reg.name
        )
    )


def optimality_norm(
    z_data: jax.Array,
    w: jax.Array,
    reg: losses_lib.Regularizer,
    eta: float,
) -> float:
    """First-order optimality residual at ``w``, given the data gradient
    ``z_data = (1/N) sum_i phi'(w^T x_i, y_i) x_i`` computed **at the same
    w** (not a stale snapshot).

    Smooth g: the plain gradient norm ``||z_data + grad g(w)||``.
    Nonsmooth g (l1 / elastic_net): the prox gradient-mapping norm
    ``||(w - prox_{eta*g}(w - eta * grad f(w))) / eta||`` — the standard
    composite-optimality measure, which specializes to the gradient norm
    when the prox is the identity.  Both vanish exactly at a minimizer.
    """
    if reg.is_smooth:
        return float(jnp.linalg.norm(z_data + reg.grad(w)))
    v = reg.prox(w - eta * (z_data + reg.smooth_grad(w)), eta)
    return float(jnp.linalg.norm((w - v) / eta))


def make_same_iterate_eval(
    labels: jax.Array,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
    eta: float,
) -> Callable:
    """The standard ``evaluate`` hook: objective from the snapshot margins,
    optimality residual from the snapshot gradient — z, s0, and w all at
    the post-epoch iterate."""

    def evaluate(w, z_data, s0):
        obj = objective_from_margins(s0, labels, w, loss, reg)
        return obj, optimality_norm(z_data, w, reg, eta)

    return evaluate


# ---------------------------------------------------------------------------
# Sample / option-mask drawing (one rng-stream convention for all drivers)
# ---------------------------------------------------------------------------


def resolve_init_w(
    init_w: jax.Array | None, dim: int, dtype, num_outputs: int = 1
) -> jax.Array:
    """The starting iterate every driver shares: zeros unless the caller
    warm-starts (``repro.api`` threads ``FDSVRGClassifier.partial_fit``'s
    coefficients through here), always in the data's dtype so a warm
    start can't silently promote a float32 run to float64.
    ``num_outputs > 1`` is the multi-output shape ``w ∈ R^{d×k}``
    (one-vs-rest / multivariate squared loss); ``1`` keeps the historical
    1-D iterate bit-for-bit."""
    shape = (dim,) if num_outputs == 1 else (dim, num_outputs)
    if init_w is None:
        return jnp.zeros(shape, dtype=dtype)
    init_w = jnp.asarray(init_w, dtype=dtype)
    if init_w.shape != shape:
        raise ValueError(
            f"init_w has shape {init_w.shape}, expected {shape}"
        )
    return init_w


def draw_samples(rng: np.random.Generator, n: int, m: int, u: int) -> np.ndarray:
    """M mini-batches of u uniform instance ids (the paper's sampling)."""
    return rng.integers(0, n, size=(m, u), dtype=np.int64).astype(np.int32)


def option_mask(rng: np.random.Generator, m: int, option: str) -> np.ndarray:
    """Step mask: Option I runs all M steps (and draws nothing from the
    rng); Option II stops at a uniform random step."""
    if option == "I":
        return np.ones(m, dtype=np.float32)
    stop = int(rng.integers(1, m + 1))
    return (np.arange(m) < stop).astype(np.float32)


# ---------------------------------------------------------------------------
# Failure semantics: recovery + checkpoint policies
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Epoch-abort-to-snapshot recovery for the outer loop.

    On any :class:`~repro.dist.FaultError` raised during an epoch (worker
    crash, retries exhausted) or by the divergence guard, the harness
    discards the failed epoch and reruns outer t from the snapshot
    (w, z, s0) it already holds — SVRG's replicated outer state makes
    this correct with no ad-hoc repair.  ``on_abort(backend)`` meters
    whatever the abort path costs (the FD drivers default it to one
    full-gradient redistribution under the ``"abort"`` kind); after
    ``max_epoch_retries`` consecutive failed attempts of the same outer,
    the fault propagates to the caller.
    """

    max_epoch_retries: int = 2  # reruns allowed per outer iteration
    eta_backoff: float = 0.5  # eta scale multiplier on divergence
    divergence_factor: float = 1e3  # obj > factor * |prev obj| => diverged
    on_abort: Callable | None = None  # on_abort(backend): meter the abort

    def __post_init__(self) -> None:
        if self.max_epoch_retries < 0:
            raise ValueError("max_epoch_retries >= 0 required")
        if not 0.0 < self.eta_backoff <= 1.0:
            raise ValueError("eta_backoff must be in (0, 1]")
        if self.divergence_factor <= 1.0:
            raise ValueError("divergence_factor > 1 required")


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """Persist outer-loop state every ``every`` outers (and at the end).

    One rolling checkpoint at ``<directory>/outer``: arrays (w, z, s0)
    in the npz, everything else — outer index, eta scale, numpy rng
    state, meter counters + event log, modeled time, history — in the
    json sidecar's ``extra`` dict.  ``resume=True`` restores all of it
    before the first epoch when the checkpoint exists (and starts fresh
    when it does not, so a first run and a restart share one flag); the
    resumed run is bit-identical to the uninterrupted one.
    """

    directory: str
    every: int = 1
    resume: bool = False

    def __post_init__(self) -> None:
        if not self.directory:
            raise ValueError("CheckpointPolicy.directory must be non-empty")
        if self.every < 1:
            raise ValueError("CheckpointPolicy.every >= 1 required")

    @property
    def path(self) -> str:
        return os.path.join(self.directory, "outer")

    def exists(self) -> bool:
        return os.path.exists(self.path + ".npz")


_CKPT_VERSION = 1


def _save_outer_state(
    policy: CheckpointPolicy,
    *,
    w,
    z_data,
    s0,
    outer_next: int,
    eta_scale: float,
    rng: np.random.Generator,
    meter: CommMeter,
    modeled_time_s: float,
    history: list[OuterRecord],
) -> None:
    ckpt.save(
        policy.path,
        {"w": w, "z": z_data, "s0": s0},
        extra={
            "version": _CKPT_VERSION,
            "outer_next": int(outer_next),
            "eta_scale": float(eta_scale),
            "rng_state": rng.bit_generator.state,
            "meter": meter.state_dict(),
            "modeled_time_s": float(modeled_time_s),
            "history": [dataclasses.asdict(h) for h in history],
        },
    )


def _outer_state_like(policy: CheckpointPolicy, w) -> dict:
    """Restore templates for (w, z, s0) with no snapshot computed: ``z``
    has ``w``'s shape and dtype, ``s0`` the checkpoint's own row count
    (which the first rotation then checks against the data's)."""
    meta = ckpt.load_meta(policy.path)
    s0_key = jax.tree_util.keystr((jax.tree_util.DictKey("s0"),))
    s0_shape = dict(zip(meta["keys"], meta["shapes"]))[s0_key]
    return {"w": w, "z": w, "s0": jax.ShapeDtypeStruct(tuple(s0_shape), w.dtype)}


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def run_outer_loop(
    *,
    outer_iters: int,
    seed: int,
    init_w: jax.Array,
    snapshot: Callable,
    epoch: Callable,
    evaluate: Callable,
    backend: Collectives | None = None,
    recovery: RecoveryPolicy | None = None,
    checkpoint: CheckpointPolicy | None = None,
    init_snapshot: tuple[jax.Array, jax.Array] | None = None,
) -> RunResult:
    """Run ``outer_iters`` outer iterations with snapshot rotation.

    Sequence per outer t: ``epoch`` consumes the current snapshot
    (z, s0) — the full gradient at the iterate entering the epoch — then
    ``snapshot`` recomputes at the post-epoch iterate, which is both the
    next outer's snapshot and the same-iterate pair ``evaluate`` reports
    from.  ``backend=None`` means no communication (the serial path):
    the history records zero scalars/rounds/modeled time against a fresh
    empty meter.

    ``recovery`` arms epoch-abort-to-snapshot: the snapshot entering the
    epoch is only rotated *after* the epoch and its evaluation succeed,
    so a failed attempt retries from exactly the state it started with.
    If the epoch hook accepts an ``eta_scale`` keyword, divergence
    backoff is threaded through it (a retried epoch reruns with a
    smaller step); hooks that don't accept it still get abort/retry.
    ``checkpoint`` arms persistence/resume (see
    :class:`CheckpointPolicy`).  ``init_snapshot`` is ``(z, s0)`` at
    ``init_w``, in the hooks' layout, as a run that ended at ``init_w``
    returned it (:attr:`RunResult.final`); given one, or resuming, the
    loop computes no full gradient before the first epoch.  Under the
    profiler each run counts one of ``snapshot0.carried`` and
    ``snapshot0.computed``.

    Under the profiler each phase is a :mod:`repro.obs` span:
    ``loop.snapshot0``, then per attempt an ``outer`` step span
    (``step_num=t``, ``attempt``) holding ``outer.snapshot``,
    ``outer.evaluate`` and ``outer.checkpoint``; the epoch hooks add
    ``outer.samples`` and ``outer.epoch``.
    """
    obs.end("solve.prepare")
    rng = np.random.default_rng(seed)
    w = init_w
    meter = backend.meter if backend is not None else CommMeter()
    history: list[OuterRecord] = []
    eta_scale = 1.0
    start_outer = 0
    accepts_scale = "eta_scale" in inspect.signature(epoch).parameters
    t_start = time.perf_counter()
    resume = checkpoint is not None and checkpoint.resume and checkpoint.exists()
    with obs.span("loop.snapshot0"):
        # The outer-0 snapshot: the checkpoint's or the caller's when
        # there is one, else the full gradient at init_w.
        if resume or init_snapshot is not None:
            obs.count("snapshot0.carried", 1)
            z_data, s0 = init_snapshot or (None, None)
        else:
            obs.count("snapshot0.computed", 1)
            z_data, s0 = snapshot(w)
    if resume:
        state = ckpt.restore(checkpoint.path, _outer_state_like(checkpoint, w))
        extra = ckpt.load_meta(checkpoint.path)["extra"]
        w, z_data, s0 = state["w"], state["z"], state["s0"]
        rng.bit_generator.state = extra["rng_state"]
        meter.load_state(extra["meter"])
        if backend is not None:
            # 0.0 + x == x bitwise, and modeled time accumulates left to
            # right, so re-charging the saved prefix then continuing is
            # exactly the uninterrupted sum.
            backend.charge_seconds(extra["modeled_time_s"])
        eta_scale = float(extra["eta_scale"])
        start_outer = int(extra["outer_next"])
        history = [OuterRecord(**h) for h in extra["history"]]
        if history:
            t_start = time.perf_counter() - history[-1].wall_time_s
    prev_obj: float | None = None
    for t in range(start_outer, outer_iters):
        attempts = 0
        while True:
            with obs.span("outer", step_num=t, attempt=attempts):
                begin_outer = getattr(backend, "begin_outer", None)
                if begin_outer is not None:
                    begin_outer(t)
                try:
                    if accepts_scale:
                        w_new = epoch(t, rng, w, z_data, s0, eta_scale=eta_scale)
                    else:
                        w_new = epoch(t, rng, w, z_data, s0)
                    # Rotation: the post-epoch full gradient is next outer's
                    # snapshot and this record's diagnostic pair (z and w at
                    # the SAME iterate).
                    with obs.span("outer.snapshot"):
                        z_new, s0_new = snapshot(w_new)
                    if s0_new.shape != s0.shape:
                        raise ValueError(
                            f"outer {t}: the margins have shape "
                            f"{s0_new.shape}, the snapshot it started from "
                            f"{s0.shape}: a checkpoint or carried snapshot "
                            "of other data"
                        )
                    with obs.span("outer.evaluate"):
                        obj, gnorm = evaluate(w_new, z_new, s0_new)
                    if recovery is not None:
                        floor = max(abs(prev_obj), 1.0) if prev_obj is not None \
                            else None
                        if not (np.isfinite(obj) and np.isfinite(gnorm)):
                            raise DivergenceError(
                                f"outer {t}: non-finite objective/optimality "
                                f"(obj={obj}, norm={gnorm})"
                            )
                        if floor is not None and \
                                obj > recovery.divergence_factor * floor:
                            raise DivergenceError(
                                f"outer {t}: objective exploded "
                                f"({obj:.3e} > {recovery.divergence_factor:g} * "
                                f"{floor:.3e})"
                            )
                except FaultError as err:
                    if recovery is None or attempts >= recovery.max_epoch_retries:
                        raise
                    attempts += 1
                    if isinstance(err, DivergenceError):
                        eta_scale *= recovery.eta_backoff
                    if recovery.on_abort is not None and backend is not None:
                        recovery.on_abort(backend)
                    # Retry from the snapshot: w/z_data/s0 were never
                    # rotated, so the failed epoch leaves no trace in the
                    # trajectory — only in the meter (retries, aborts) and
                    # modeled time.
                    continue
                w, z_data, s0 = w_new, z_new, s0_new
                prev_obj = obj
                history.append(
                    OuterRecord(
                        t,
                        obj,
                        gnorm,
                        meter.total_scalars,
                        meter.total_rounds,
                        backend.modeled_time_s if backend is not None else 0.0,
                        time.perf_counter() - t_start,
                    )
                )
                if checkpoint is not None and (
                    (t + 1) % checkpoint.every == 0 or t == outer_iters - 1
                ):
                    with obs.span("outer.checkpoint"):
                        _save_outer_state(
                            checkpoint,
                            w=w,
                            z_data=z_data,
                            s0=s0,
                            outer_next=t + 1,
                            eta_scale=eta_scale,
                            rng=rng,
                            meter=meter,
                            modeled_time_s=(
                                backend.modeled_time_s
                                if backend is not None else 0.0
                            ),
                            history=history,
                        )
            break
    return RunResult(
        w=w, history=history, meter=meter, final=Snapshot(w, z_data, s0)
    )
