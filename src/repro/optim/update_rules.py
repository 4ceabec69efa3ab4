"""The pluggable update-rule layer: one outer-loop harness, many inner steps.

FD-SVRG is one point in a family of feature-distributed variance-reduced
methods.  What they share is the *shape* the harness
(:func:`repro.core.driver.run_outer_loop`) expects — a ``snapshot`` hook,
an ``epoch`` hook, an ``evaluate`` hook — and the BlockCSR block-local
layout.  What differs is everything an :class:`UpdateRule` owns:

* **per-step state init/carry** — SVRG carries nothing beyond the
  harness's replicated snapshot pair ``(z, s0)``; SAGA carries the
  per-sample scalar gradient table ``α ∈ R^n`` and its running mean
  ``z = (1/n) Σ α_i x_i``; BCD carries the active-block cursor and the
  maintained margins;
* **the variance-reduced direction** — SVRG's
  ``(φ'(s_m) − φ'(s̃_m)) x + z``, SAGA's ``(α_new − α_old) x + z``,
  BCD's full block gradient;
* **the communication it implies** — metered/charged inside the rule's
  ``epoch`` against the §4.5-style closed forms in
  :data:`repro.dist.COSTS`, so the drift guard pins every rule's meter
  to its analytic schedule the same way.

:class:`SVRGRule` is the extraction of the exact code the drivers
``run_serial_svrg`` / ``run_fdsvrg`` used to inline — same jitted scans
(:func:`repro.core.fdsvrg._inner_epoch` and friends stay where the
worker simulation shares them), same metering order, bit-identical by
construction and pinned in ``tests/test_update_rules.py``.

Multi-output ``w ∈ R^{d×k}`` rides the SVRG rule: a ``[N, k]`` label
matrix (e.g. the estimator's one-vs-rest coding, or multivariate squared
loss) vmaps the same jitted epoch over the trailing output axis — one
data matrix, one margin tree per batch carrying ``u·k`` scalars.  ``k=1``
keeps the historical 1-D path untouched (a ``[N, 1]`` label matrix is
squeezed before any compute), so binary runs are bitwise identical.

Import direction: the block update every rule's step ends in
(:func:`repro.data.block_csr.block_prox_update`, shared with the worker
simulation and the mesh) sits below both layers.  This module still
imports the jitted scans *from* :mod:`repro.core.fdsvrg`, and the
drivers there import this module lazily inside their function bodies:
``repro.core.__init__`` eagerly imports ``fdsvrg``, so a module-level
import back into ``repro.optim`` from there would see the
partially-initialized module.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import losses as losses_lib
from repro.core.driver import (
    CheckpointPolicy,
    RecoveryPolicy,
    RunResult,
    Snapshot,
    draw_samples,
    make_same_iterate_eval,
    optimality_norm,
    option_mask,
    resolve_init_w,
    run_outer_loop,
)
from repro.core.fdsvrg import (
    SVRGConfig,
    _bounds,
    _check_lazy,
    _default_fd_abort,
    _full_grad_blocks,
    _inner_epoch,
    _kernel_lams,
    _lazy_corrections,
    _lazy_inner_epoch,
)
from repro.data.block_csr import (
    BlockCSR, block_prox_update, local_margins, local_scatter,
)
from repro.dist import COSTS, Collectives, tree_order_sum


# ---------------------------------------------------------------------------
# Context: everything a rule needs to build its hooks
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RuleContext:
    """One run's immutable inputs, handed to :meth:`UpdateRule.build_*`.

    ``backend=None`` is the serial (unmetered) path — rules must meter
    and charge only when a backend is present, exactly like the
    pre-refactor drivers.  ``num_outputs`` is the trailing output width
    k; 1 is the scalar path (labels are 1-D)."""

    block_data: BlockCSR
    loss: losses_lib.MarginLoss
    reg: losses_lib.Regularizer
    cfg: SVRGConfig
    backend: Collectives | None = None
    num_outputs: int = 1

    @property
    def labels(self) -> jax.Array:
        return self.block_data.labels

    @property
    def n(self) -> int:
        return self.block_data.num_instances

    @property
    def q(self) -> int:
        return self.block_data.num_blocks

    @property
    def u(self) -> int:
        return self.cfg.batch_size

    @property
    def nnz(self) -> int:
        return self.block_data.global_nnz_max()

    @property
    def dtype(self):
        return self.block_data.values[0].dtype


def make_context(
    block_data: BlockCSR,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
    cfg: SVRGConfig,
    *,
    backend: Collectives | None = None,
) -> RuleContext:
    """Build a :class:`RuleContext`, deriving the output width from the
    labels: a ``[N, k]`` label matrix means ``w ∈ R^{d×k}``; ``[N, 1]``
    is squeezed onto the scalar path so k=1 stays bitwise identical to a
    1-D label run."""
    labels = block_data.labels
    num_outputs = 1
    if getattr(labels, "ndim", 1) == 2:
        num_outputs = int(labels.shape[1])
        if num_outputs == 1:
            block_data = dataclasses.replace(block_data, labels=labels[:, 0])
            num_outputs = 1
    if backend is not None and backend.q != block_data.num_blocks:
        raise ValueError(
            f"backend has q={backend.q} workers but block_data has "
            f"{block_data.num_blocks} blocks"
        )
    return RuleContext(
        block_data=block_data,
        loss=loss,
        reg=reg,
        cfg=cfg,
        backend=backend,
        num_outputs=num_outputs,
    )


def _full_grad_lanes(bd: BlockCSR) -> tuple[int, int]:
    """``(lanes, stored)`` one full gradient over ``bd`` processes: each
    block's row groups, N_b rows times width W_b, summed over groups and
    blocks, and the stored entries among them (read from the layout, no
    device work)."""
    return sum(g.lanes for g in bd.groups), bd.stored


def _full_grad_snapshot(bd: BlockCSR, loss_name: str, use_kernels: bool) -> Callable:
    """The ``snapshot`` hook over :func:`_full_grad_blocks`; under the
    profiler each dispatch adds its lanes and stored entries to the
    ``full_grad.lanes`` / ``full_grad.stored`` counters (:mod:`repro.obs`)."""
    lanes, stored = _full_grad_lanes(bd)

    def snapshot(w):
        obs.count("full_grad.lanes", lanes)
        obs.count("full_grad.stored", stored)
        return _full_grad_blocks(
            bd.groups, bd.labels, w, loss_name, bd.block_dims, use_kernels,
        )

    return snapshot


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------


class UpdateRule:
    """Base class: a rule owns its state carry, direction, and comm.

    ``build_snapshot`` / ``build_epoch`` / ``build_evaluate`` are called
    once per run and return the harness hooks; state that must carry
    *across* epochs but is not part of the harness's replicated snapshot
    (SAGA's table, BCD's cursor) lives in the epoch closure.  The
    capability flags mirror the registry's :class:`MethodInfo` record —
    :func:`run_with_rule` enforces them for direct (non-registry)
    callers too.
    """

    name: str = "update_rule"
    supports_recovery: bool = False  # epoch-abort-to-snapshot retries
    supports_checkpoint: bool = False
    supports_multi_output: bool = False
    supports_option_ii: bool = False

    def validate(self, ctx: RuleContext) -> None:
        if ctx.num_outputs > 1 and not self.supports_multi_output:
            raise ValueError(
                f"rule {self.name!r} does not support multi-output labels "
                f"(got a [N, {ctx.num_outputs}] label matrix)"
            )
        if ctx.cfg.option == "II" and not self.supports_option_ii:
            raise ValueError(
                f"rule {self.name!r} runs Option I only; option='II' "
                "would not be honored"
            )

    def build_snapshot(self, ctx: RuleContext) -> Callable:
        raise NotImplementedError

    def build_epoch(self, ctx: RuleContext) -> Callable:
        raise NotImplementedError

    def build_evaluate(self, ctx: RuleContext) -> Callable:
        return make_same_iterate_eval(ctx.labels, ctx.loss, ctx.reg, ctx.cfg.eta)

    def build_init_w(self, ctx: RuleContext, init_w) -> jax.Array:
        return resolve_init_w(
            init_w, ctx.block_data.dim, ctx.dtype, ctx.num_outputs
        )

    def default_abort(self, ctx: RuleContext) -> Callable | None:
        return None


def run_with_rule(
    rule: UpdateRule,
    ctx: RuleContext,
    *,
    init_w=None,
    recovery: RecoveryPolicy | None = None,
    checkpoint: CheckpointPolicy | None = None,
    start: Snapshot | None = None,
) -> RunResult:
    """Wire one rule into the ONE outer-loop harness and run it.

    ``start`` is the :attr:`RunResult.final` of a run on the same layout
    that ended where this one begins: its ``w`` is the starting iterate
    and its pair the outer-0 snapshot, so no full gradient is computed
    before the first epoch."""
    rule.validate(ctx)
    if recovery is not None and not rule.supports_recovery:
        raise ValueError(
            f"rule {rule.name!r} does not support epoch-abort recovery: "
            "its carried state (gradient table / block cursor) advances "
            "inside the epoch, so a snapshot retry would replay against "
            "mutated state"
        )
    if checkpoint is not None and not rule.supports_checkpoint:
        raise ValueError(
            f"rule {rule.name!r} does not support checkpoint/resume: the "
            "harness checkpoint only persists (w, z, s0), not the rule's "
            "carried state"
        )
    if recovery is not None and recovery.on_abort is None \
            and ctx.backend is not None:
        on_abort = rule.default_abort(ctx)
        if on_abort is not None:
            recovery = dataclasses.replace(recovery, on_abort=on_abort)
    return run_outer_loop(
        outer_iters=ctx.cfg.outer_iters,
        seed=ctx.cfg.seed,
        init_w=rule.build_init_w(ctx, init_w if start is None else start.w),
        snapshot=rule.build_snapshot(ctx),
        epoch=rule.build_epoch(ctx),
        evaluate=rule.build_evaluate(ctx),
        backend=ctx.backend,
        recovery=recovery,
        checkpoint=checkpoint,
        init_snapshot=None if start is None else (start.z, start.s0),
    )


# ---------------------------------------------------------------------------
# SVRG (the extracted rule — bit-identical to the pre-refactor drivers)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SVRGRule(UpdateRule):
    """Prox-SVRG: snapshot pair (z, s0) is the whole state; the harness's
    rotation carries it.  ``use_kernels`` / ``lazy_updates`` select the
    fused-Pallas and delayed-decay inner scans exactly as the drivers'
    keyword arguments always did (scalar path only — the kernels have no
    trailing output axis)."""

    use_kernels: bool = False
    lazy_updates: str | None = None

    name = "svrg"
    supports_recovery = True
    supports_checkpoint = True
    supports_multi_output = True
    supports_option_ii = True

    def validate(self, ctx: RuleContext) -> None:
        super().validate(ctx)
        _check_lazy(self.lazy_updates)
        if ctx.num_outputs > 1 and (self.use_kernels or self.lazy_updates):
            raise ValueError(
                "multi-output labels run the jnp inner step only: "
                "use_kernels/lazy_updates have no trailing-k kernels "
                f"(got k={ctx.num_outputs})"
            )

    def default_abort(self, ctx: RuleContext) -> Callable | None:
        return _default_fd_abort(
            ctx.n * ctx.num_outputs, ctx.nnz, ctx.q
        )

    def build_snapshot(self, ctx: RuleContext) -> Callable:
        bd, loss_name, k = ctx.block_data, ctx.loss.name, ctx.num_outputs
        if k == 1:
            return _full_grad_snapshot(bd, loss_name, self.use_kernels)

        def one(labels_j, w_j):
            return _full_grad_blocks(
                bd.groups, labels_j, w_j, loss_name, bd.block_dims, False,
            )

        multi = jax.vmap(one, in_axes=(1, 1), out_axes=(1, 1))
        lanes, stored = _full_grad_lanes(bd)

        def snapshot_multi(w):
            obs.count("full_grad.lanes", k * lanes)
            obs.count("full_grad.stored", k * stored)
            return multi(bd.labels, w)

        return snapshot_multi

    def build_epoch(self, ctx: RuleContext) -> Callable:
        bd, cfg, backend, loss, reg = (
            ctx.block_data, ctx.cfg, ctx.backend, ctx.loss, ctx.reg,
        )
        use_kernels, lazy_updates = self.use_kernels, self.lazy_updates
        kernel_lams = _kernel_lams(reg, use_kernels)
        corrections = _lazy_corrections(bd, ctx.n, ctx.u, lazy_updates)
        n, u, nnz, q, k = ctx.n, ctx.u, ctx.nnz, ctx.q, ctx.num_outputs
        labels, block_dims = bd.labels, bd.block_dims

        multi_epoch = _bind_multi_epoch(ctx) if k > 1 else None

        def epoch(t, rng, w, z_data, s0, eta_scale=1.0):
            # --- full-gradient phase (Alg 1 lines 3-5): account the
            # snapshot gradient this outer iteration consumes ---
            if backend is not None:
                backend.meter_tree(payload=n * k)
                backend.charge_cost(COSTS.fd_fullgrad(n=n, nnz=nnz, q=q, k=k))
            # eta stays a traced operand, so divergence backoff
            # (eta_scale < 1) reuses the compiled scan; eta * 1.0 is
            # bit-exact on the default path.
            eta = cfg.eta * eta_scale
            with obs.span("outer.samples"):
                samples = jnp.asarray(draw_samples(rng, n, cfg.inner_steps, u))
                mask = jnp.asarray(option_mask(rng, cfg.inner_steps, cfg.option))
            with obs.span("outer.epoch"):
                if multi_epoch is not None:
                    w = multi_epoch(labels, w, z_data, s0, samples, eta, mask)
                elif lazy_updates is not None:
                    w = _lazy_inner_epoch(
                        bd.indices, bd.values, labels,
                        w, z_data, s0, samples, eta, mask,
                        corrections, loss.name, reg.name, reg.lam, block_dims,
                        use_kernels, lazy_updates, lam2=reg.lam2,
                        kernel_lams=kernel_lams,
                    )
                else:
                    w = _inner_epoch(
                        bd.indices, bd.values, labels,
                        w, z_data, s0, samples, eta, mask,
                        loss.name, reg.name, reg.lam, block_dims, use_kernels,
                        lam2=reg.lam2, kernel_lams=kernel_lams,
                    )
            # --- inner-loop communication (Alg 1 lines 9-11): one tree
            # round per mini-batch of u·k margins; M steps, in aggregate.
            if backend is not None:
                backend.meter_tree(payload=u * k, steps=cfg.inner_steps)
                backend.charge_cost(
                    COSTS.fd_inner_step(nnz=nnz, q=q, u=u, k=k),
                    steps=cfg.inner_steps,
                )
            return w

        return epoch

    def build_evaluate(self, ctx: RuleContext) -> Callable:
        if ctx.num_outputs == 1:
            return super().build_evaluate(ctx)
        labels, loss, reg, eta, k = (
            ctx.labels, ctx.loss, ctx.reg, ctx.cfg.eta, ctx.num_outputs,
        )

        def evaluate(w, z_data, s0):
            # Mean-per-output objective: the data term averages over all
            # N·k margins, so g(w) is divided by k to match — for k=1
            # this is exactly the scalar objective, and for independent
            # columns it is the average of the k per-column objectives.
            obj = float(
                jnp.mean(loss.value(s0, labels)) + reg.value(w) / k
            )
            return obj, optimality_norm(z_data, w, reg, eta)

        return evaluate


def _bind_multi_epoch(ctx: RuleContext) -> Callable:
    """vmap the scalar jnp inner epoch over the trailing output axis:
    labels/w/z/s0 batch on axis 1, the sample stream and step mask are
    shared (one margin tree per batch carries u·k scalars)."""
    bd, loss, reg = ctx.block_data, ctx.loss, ctx.reg
    block_dims = bd.block_dims

    def one(labels_j, w_j, z_j, s0_j, samples, eta, mask):
        return _inner_epoch(
            bd.indices, bd.values, labels_j, w_j, z_j, s0_j,
            samples, eta, mask,
            loss.name, reg.name, reg.lam, block_dims, False,
            lam2=reg.lam2, kernel_lams=None,
        )

    return jax.vmap(
        one, in_axes=(1, 1, 1, 1, None, None, None), out_axes=1
    )


# ---------------------------------------------------------------------------
# FD-SAGA: replicated scalar gradient table (n floats, never d)
# ---------------------------------------------------------------------------


# lam traced / lam2 static, mirroring _inner_epoch (lambda sweeps reuse
# one compiled scan).
@functools.partial(
    jax.jit, static_argnames=("loss_name", "reg_name", "block_dims", "lam2")
)
def _saga_inner_epoch(
    block_indices,  # per-block int32[N, nnz_l], LOCAL ids
    block_values,  # per-block float[N, nnz_l]
    labels,
    w0,
    z0,  # running table mean (1/n) sum_i alpha_i x_i, concatenated blocks
    alpha0,  # float[n] per-sample margin-derivative table
    samples,  # int32[M, u]
    eta,
    loss_name: str,
    reg_name: str,
    lam,
    block_dims: tuple[int, ...],
    lam2: float = 0.0,
):
    """M FD-SAGA steps on the block-local layout.

    Per step: the sampled margins are computed the feature-distributed
    way (per-block partial dots summed in tree order — u scalars on the
    wire, same schedule as the SVRG step), the direction is
    ``mean_i (α_new_i − α_old_i) x_i + z + ∇g_smooth`` followed by the
    prox, and the table/mean are updated in place.  The table is *per
    sample* scalars, so every worker holds all n floats (replicating it
    costs one N-payload tree at init); the mean z is feature-partitioned
    like w.  Duplicate draws inside one mini-batch count toward the
    direction (iid sampling keeps it unbiased) but only their first
    occurrence updates the table and its mean, so the invariant
    ``z == (1/n) Σ α_i x_i`` holds exactly at every step.
    """
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    u = samples.shape[1]
    n = labels.shape[0]
    q = len(block_dims)
    bounds = _bounds(block_dims)

    def step(carry, ids):
        w, z, alpha = carry
        y = labels[ids]
        rows = [(block_indices[l][ids], block_values[l][ids]) for l in range(q)]
        parts = [
            local_margins(
                rows[l][0], rows[l][1],
                jax.lax.slice_in_dim(w, bounds[l], bounds[l + 1]),
            )
            for l in range(q)
        ]
        s_m = tree_order_sum(parts)
        a_new = loss.dvalue(s_m, y)
        delta = a_new - alpha[ids]
        # First-occurrence mask over the u drawn ids (u is small; the
        # u×u comparison is trivial) — duplicates must not double-count
        # in the table mean.
        eq = ids[:, None] == ids[None, :]
        is_first = jnp.argmax(eq, axis=1) == jnp.arange(u)
        coef_dir = delta / u
        coef_tab = jnp.where(is_first, delta, 0.0) / n
        new_w, new_z = [], []
        for l in range(q):
            idx, val = rows[l]
            w_blk = jax.lax.slice_in_dim(w, bounds[l], bounds[l + 1])
            z_blk = jax.lax.slice_in_dim(z, bounds[l], bounds[l + 1])
            new_w.append(
                block_prox_update(w_blk, idx, val, coef_dir, z_blk, eta, reg)
            )
            new_z.append(
                z_blk + local_scatter(idx, val, coef_tab, block_dims[l])
            )
        w_next = jnp.concatenate(new_w) if q > 1 else new_w[0]
        z_next = jnp.concatenate(new_z) if q > 1 else new_z[0]
        alpha_next = alpha.at[ids].set(a_new)
        return (w_next, z_next, alpha_next), None

    (w_final, z_final, alpha_final), _ = jax.lax.scan(
        step, (w0, z0, alpha0), samples
    )
    return w_final, z_final, alpha_final


class SAGARule(UpdateRule):
    """Feature-distributed SAGA (Distributed SAGA, arXiv 1705.10405).

    State carry: the n-float margin-derivative table α and its running
    mean z, initialized from the outer-0 harness snapshot — ``α =
    φ'(s0, y)`` and ``z = z_data`` are *exactly* the snapshot pair's
    content, so initialization is one full-gradient-shaped phase
    (:meth:`CostModel.fd_saga_init`), charged once.  After that no
    full-gradient phase ever recurs: the harness's per-outer snapshots
    are reporting-only (compute, never metered), and each of the M
    steps meters one u-payload tree + 3 sparse passes
    (:meth:`CostModel.fd_saga_step`).
    """

    name = "fd_saga"
    supports_recovery = False  # the table advances inside the epoch
    supports_checkpoint = False
    supports_multi_output = False
    supports_option_ii = False

    def build_snapshot(self, ctx: RuleContext) -> Callable:
        return _full_grad_snapshot(ctx.block_data, ctx.loss.name, False)

    def build_epoch(self, ctx: RuleContext) -> Callable:
        bd, cfg, backend, loss, reg = (
            ctx.block_data, ctx.cfg, ctx.backend, ctx.loss, ctx.reg,
        )
        n, u, nnz, q = ctx.n, ctx.u, ctx.nnz, ctx.q
        labels, block_dims = bd.labels, bd.block_dims
        state: dict = {}

        def epoch(t, rng, w, z_data, s0, eta_scale=1.0):
            if "alpha" not in state:
                # Outer 0: adopt the harness snapshot as the table —
                # z_data IS (1/n) Σ φ'(s0_i, y_i) x_i, bit-for-bit.
                state["alpha"] = loss.dvalue(s0, labels)
                state["z"] = z_data
                if backend is not None:
                    backend.meter_tree(payload=n)
                    backend.charge_cost(COSTS.fd_saga_init(n=n, nnz=nnz, q=q))
            eta = cfg.eta * eta_scale
            with obs.span("outer.samples"):
                samples = jnp.asarray(draw_samples(rng, n, cfg.inner_steps, u))
            with obs.span("outer.epoch"):
                w, z, alpha = _saga_inner_epoch(
                    bd.indices, bd.values, labels,
                    w, state["z"], state["alpha"], samples, eta,
                    loss.name, reg.name, reg.lam, block_dims, lam2=reg.lam2,
                )
            state["z"], state["alpha"] = z, alpha
            if backend is not None:
                backend.meter_tree(payload=u, steps=cfg.inner_steps)
                backend.charge_cost(
                    COSTS.fd_saga_step(nnz=nnz, q=q, u=u),
                    steps=cfg.inner_steps,
                )
            return w

        return epoch


# ---------------------------------------------------------------------------
# FD-BCD: distributed block coordinate descent (Mahajan et al., 1405.4544)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("loss_name", "reg_name", "lo", "block_dim", "lam2"),
)
def _bcd_block_step(
    idx,  # int32[N, nnz_l] LOCAL ids of the active block
    val,  # float[N, nnz_l]
    labels,
    w,
    s,  # float[N] maintained margins (replicated)
    eta,
    loss_name: str,
    reg_name: str,
    lam,
    lo: int,
    block_dim: int,
    lam2: float = 0.0,
):
    """One BCD step: the active worker takes a prox-gradient step on its
    whole block against the full data gradient restricted to it, then
    the margin delta of the block update is tree-replicated so every
    worker's maintained margins stay exact."""
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    n = labels.shape[0]
    coeffs = loss.dvalue(s, labels) / n
    w_blk = jax.lax.slice_in_dim(w, lo, lo + block_dim)
    g = local_scatter(idx, val, coeffs, block_dim) + reg.smooth_grad(w_blk)
    w_new_blk = reg.prox(w_blk - eta * g, eta)
    s_next = s + local_margins(idx, val, w_new_blk - w_blk)
    w_next = jax.lax.dynamic_update_slice_in_dim(w, w_new_blk, lo, axis=0)
    return w_next, s_next


class BCDRule(UpdateRule):
    """Distributed block coordinate descent — the paper's natural L1
    competitor (Mahajan et al., arXiv 1405.4544), on the same BlockCSR
    column partition as FD-SVRG.

    State carry: the active-block cursor (cycling; it survives across
    outers so M need not be a multiple of q) plus the maintained margins
    — re-seeded each epoch from the harness snapshot's ``s0``, which is
    exactly the margins at the epoch-entry iterate.  Each step meters
    one N-payload tree (the block's margin delta must reach every
    worker); the sample stream is untouched (BCD is deterministic)."""

    name = "fd_bcd"
    supports_recovery = False  # the cursor advances inside the epoch
    supports_checkpoint = False
    supports_multi_output = False
    supports_option_ii = False

    def build_snapshot(self, ctx: RuleContext) -> Callable:
        return _full_grad_snapshot(ctx.block_data, ctx.loss.name, False)

    def build_epoch(self, ctx: RuleContext) -> Callable:
        bd, cfg, backend, loss, reg = (
            ctx.block_data, ctx.cfg, ctx.backend, ctx.loss, ctx.reg,
        )
        n, nnz, q = ctx.n, ctx.nnz, ctx.q
        labels, block_dims = bd.labels, bd.block_dims
        bounds = _bounds(block_dims)
        state = {"cursor": 0}

        def epoch(t, rng, w, z_data, s0, eta_scale=1.0):
            eta = cfg.eta * eta_scale
            s = s0
            with obs.span("outer.epoch"):
                for m in range(cfg.inner_steps):
                    l = (state["cursor"] + m) % q
                    idx, val = bd.block(l)
                    w, s = _bcd_block_step(
                        idx, val, labels, w, s, eta,
                        loss.name, reg.name, reg.lam,
                        bounds[l], block_dims[l], lam2=reg.lam2,
                    )
            state["cursor"] = (state["cursor"] + cfg.inner_steps) % q
            if backend is not None:
                backend.meter_tree(payload=n, steps=cfg.inner_steps)
                backend.charge_cost(
                    COSTS.fd_bcd_step(n=n, nnz=nnz, q=q),
                    steps=cfg.inner_steps,
                )
            return w

        return epoch


RULES = {
    "svrg": SVRGRule,
    "fd_saga": SAGARule,
    "fd_bcd": BCDRule,
}

__all__ = [
    "BCDRule",
    "RULES",
    "RuleContext",
    "SAGARule",
    "SVRGRule",
    "UpdateRule",
    "make_context",
    "run_with_rule",
]
