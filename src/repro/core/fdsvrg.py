"""FD-SVRG (paper Algorithm 1) and serial SVRG (paper Algorithm 2).

Three implementations, one update rule:

* :func:`run_serial_svrg` — Algorithm 2 (Johnson & Zhang), options I/II,
  jitted ``lax.scan`` inner loop.  This is the reference the paper proves
  FD-SVRG equivalent to.
* :func:`run_fdsvrg` — Algorithm 1 at simulation level: numerics follow
  the feature-decomposed computation (margins as a sum of per-block
  partials), communication is metered with the paper's exact accounting
  and modeled time is charged from the shared closed forms
  (:data:`repro.dist.COSTS`).
* :func:`fdsvrg_worker_simulation` — an explicit q-worker object-level
  simulation (each worker only ever touches its own ``w^(l)`` and
  ``D^(l)``); slow, used by tests to certify exact equivalence.

All three drivers run on the ONE outer-loop engine
(:func:`repro.core.driver.run_outer_loop`): snapshot rotation, sample
drawing, same-iterate reporting, and history construction live there,
not here — each implementation supplies only its ``snapshot`` and
``epoch`` hooks.

All three run on the block-local layout
(:class:`repro.data.block_csr.BlockCSR`): each worker's rows carry only
its own block's entries with local ids, so per-worker gather/scatter work
is O(nnz_max/q) — no membership masks anywhere on the hot path.  Every
implementation takes ``use_kernels``: ``True`` routes the two hot paths
through the fused Pallas kernels (:func:`repro.kernels.ops.sparse_margins`
and :func:`repro.kernels.ops.fused_block_prox_update`, interpret-mode on
CPU), ``False`` is the pure-jnp numerics oracle.  The two paths are
bit-identical in interpret mode (asserted in tests), for every
regularizer: l2, l1, elastic_net, and none (the inner step is the
Prox-SVRG update, which specializes to classic SVRG when the prox is the
identity).

All communication — executed or modeled — goes through a
:class:`repro.dist.Collectives` backend, so FD-SVRG and the baselines in
:mod:`repro.core.baselines` report bytes and modeled wall-clock through
the same meter.  The deployable TPU version (shard_map over the ``model``
mesh axis) lives in :mod:`repro.core.fdsvrg_shardmap`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import losses as losses_lib
from repro.core.driver import (
    CheckpointPolicy,
    OuterRecord,
    RecoveryPolicy,
    RunResult,
    Snapshot,
    draw_samples,
    make_same_iterate_eval,
    objective_from_margins,
    optimality_norm,
    option_mask,
    resolve_init_w,
    run_outer_loop,
)
from repro.core.partition import FeaturePartition, balanced
from repro.dist import COSTS, ClusterModel, Collectives, SimBackend, tree_order_sum
from repro.data.sparse import PaddedCSR, margins_rows, scatter_grad
from repro.data.block_csr import (
    BlockCSR, block_margins, group_margins, group_scatter, local_scatter,
)
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class SVRGConfig:
    eta: float
    inner_steps: int  # M; paper sets M = #instances held per worker (= N for FD)
    outer_iters: int
    batch_size: int = 1  # u, the mini-batch trick of §4.4.1
    option: str = "I"  # paper proves Option I (Theorem 1) and uses it
    seed: int = 0

    def __post_init__(self) -> None:
        if self.option not in ("I", "II"):
            raise ValueError(f"option must be 'I' or 'II', got {self.option!r}")
        if self.batch_size < 1:
            raise ValueError("batch_size >= 1 required")


# ---------------------------------------------------------------------------
# Objective / full gradient
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("loss_name", "reg_name"))
def _objective_impl(indices, values, labels, w, lam, lam2, loss_name, reg_name):
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    s = margins_rows(indices, values, w)
    return jnp.mean(loss.value(s, labels)) + reg.value(w)


def objective(
    data: PaddedCSR, w: jax.Array, loss: losses_lib.MarginLoss, reg: losses_lib.Regularizer
) -> float:
    return float(
        _objective_impl(
            data.indices, data.values, data.labels, w, reg.lam, reg.lam2,
            loss.name, reg.name,
        )
    )


@functools.partial(jax.jit, static_argnames=("loss_name",))
def _full_grad_impl(indices, values, labels, w, loss_name):
    """Data part of the full gradient plus the cached margins s0 = w^T x_i."""
    loss = losses_lib.LOSSES[loss_name]
    s0 = margins_rows(indices, values, w)
    coeffs = loss.dvalue(s0, labels) / labels.shape[0]
    z_data = scatter_grad(indices, values, coeffs, w.shape[0])
    return z_data, s0


def full_gradient(
    data: PaddedCSR, w: jax.Array, loss: losses_lib.MarginLoss
) -> tuple[jax.Array, jax.Array]:
    return _full_grad_impl(data.indices, data.values, data.labels, w, loss.name)


# ---------------------------------------------------------------------------
# Block-local hot paths (shared by every implementation)
# ---------------------------------------------------------------------------


def _bounds(block_dims: tuple[int, ...]) -> tuple[int, ...]:
    b = [0]
    for d in block_dims:
        b.append(b[-1] + d)
    return tuple(b)


@functools.partial(
    jax.jit, static_argnames=("loss_name", "block_dims", "use_kernels")
)
def _full_grad_blocks(
    block_groups, labels, w, loss_name, block_dims, use_kernels
):
    """Feature-decomposed full gradient: per-block partial margins summed
    in tree order (Alg 1 lines 3-4), then a purely block-local scatter
    (line 5), both over each block's row groups (``BlockCSR.groups``), so
    only the lanes of each row's length class are touched.  Returns the
    concatenated z and the cached margins s0, in row order."""
    loss = losses_lib.LOSSES[loss_name]
    q = len(block_dims)
    bounds = _bounds(block_dims)
    with jax.named_scope("full_grad/margins"):
        parts = [
            group_margins(
                block_groups[l],
                jax.lax.slice_in_dim(w, bounds[l], bounds[l + 1]),
                use_kernels,
            )
            for l in range(q)
        ]
        s0 = tree_order_sum(parts)
    with jax.named_scope("full_grad/scatter"):
        coeffs = loss.dvalue(s0, labels) / labels.shape[0]
        z_blocks = [
            group_scatter(block_groups[l], coeffs, block_dims[l])
            for l in range(q)
        ]
    z_data = jnp.concatenate(z_blocks) if q > 1 else z_blocks[0]
    return z_data, s0


def _default_fd_abort(n: int, nnz: int, q: int):
    """The default ``RecoveryPolicy.on_abort`` for the FD drivers: an
    epoch abort re-establishes the snapshot on the restarted worker —
    one extra full-gradient phase, metered under its own ``"abort"``
    kind so honest-accounting tests can separate it from the schedule."""
    from repro.dist import tree_rounds

    def on_abort(backend):
        if backend.q > 1:
            backend.p2p(2 * backend.q * n, "abort", rounds=tree_rounds(backend.q))
        backend.charge_cost(COSTS.fd_fullgrad(n=n, nnz=nnz, q=q))

    return on_abort


def _with_default_abort(
    recovery: RecoveryPolicy | None, n: int, nnz: int, q: int
) -> RecoveryPolicy | None:
    if recovery is None or recovery.on_abort is not None:
        return recovery
    return dataclasses.replace(
        recovery, on_abort=_default_fd_abort(n, nnz, q)
    )


def _kernel_lams(
    reg: losses_lib.Regularizer, use_kernels: bool
) -> tuple[float, float, float] | None:
    """Static (smooth_lam, prox_l1, prox_l2) for the fused Pallas kernels
    (compile-time constants of the run), or None on the jnp path — where
    lam stays a traced operand so lambda sweeps reuse one compilation."""
    if not use_kernels:
        return None
    return (reg.smooth_lam, reg.prox_l1, reg.prox_l2)


# ---------------------------------------------------------------------------
# Inner epoch (shared by serial and simulated-FD paths)
# ---------------------------------------------------------------------------


# lam stays traced (it only enters jnp arithmetic) so lambda sweeps reuse
# one compiled scan — matching _async_epoch, which always traced it; lam2
# is Python-branched in Regularizer.prox and must stay static.  The fused
# Pallas kernels bake their lams in at compile time, so the kernel path
# receives them separately as the static `kernel_lams` triple.
@functools.partial(
    jax.jit,
    static_argnames=(
        "loss_name", "reg_name", "block_dims", "use_kernels", "lam2",
        "kernel_lams",
    ),
)
def _inner_epoch(
    block_indices,  # per-block int32[N, nnz_l], LOCAL ids
    block_values,  # per-block float[N, nnz_l]
    labels,
    w0,
    z_data,
    s0,
    samples,  # int32[M, u]
    eta,
    step_mask,  # float32[M] (1 = apply update; Option II masks the tail)
    loss_name: str,
    reg_name: str,
    lam,  # traced regularizer strength
    block_dims: tuple[int, ...],
    use_kernels: bool,
    lam2: float = 0.0,  # elastic-net L2 strength (trailing: legacy call sites)
    kernel_lams: tuple[float, float, float] | None = None,
):
    """M proximal variance-reduced updates on the block-local layout.

    The margin of each sampled instance is computed the
    feature-distributed way: q per-block partial dots (local gathers, no
    masks) summed in block order (matching the tree reduce), certifying
    the decomposition the paper relies on.  The update is the Prox-SVRG
    step ``w <- prox_{eta*g}(w - eta * (grad_vr + z + smooth_grad g))``;
    for the smooth family the prox is the identity and this is exactly
    the classic SVRG step, bit-for-bit.  The prox is elementwise (paper
    eq. 3: g decomposes over blocks), hence purely block-local — no extra
    communication relative to the L2 path.  ``len(block_dims) == 1`` is
    the serial path.  ``use_kernels`` swaps the gather-margin and the
    scatter+prox-update for the fused Pallas kernels and requires the
    static ``kernel_lams`` triple (see :func:`_kernel_lams`).
    """
    if use_kernels and kernel_lams is None:
        raise ValueError(
            "use_kernels=True requires kernel_lams=(smooth_lam, prox_l1, "
            "prox_l2) — the fused kernels bake them in at compile time"
        )
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    u = samples.shape[1]
    q = len(block_dims)
    bounds = _bounds(block_dims)

    def step(w, inp):
        ids, mask = inp  # ids: int32[u]
        with jax.named_scope("inner/gather"):
            y = labels[ids]
            rows = [(block_indices[l][ids], block_values[l][ids])
                    for l in range(q)]
            parts = [
                block_margins(
                    rows[l][0],
                    rows[l][1],
                    jax.lax.slice_in_dim(w, bounds[l], bounds[l + 1]),
                    use_kernels,
                )
                for l in range(q)
            ]
            # Pairwise summation mirroring Figure 5 exactly (shared with
            # the simulation and interpret backends, so floating point
            # matches).
            s_m = tree_order_sum(parts)
            s_anchor = s0[ids]
        coef = (loss.dvalue(s_m, y) - loss.dvalue(s_anchor, y)) / u
        eta_m = eta * mask
        new_blocks = []
        for l in range(q):
            idx, val = rows[l]
            w_blk = jax.lax.slice_in_dim(w, bounds[l], bounds[l + 1])
            z_blk = jax.lax.slice_in_dim(z_data, bounds[l], bounds[l + 1])
            if use_kernels:
                k_lam, k_l1, k_l2 = kernel_lams
                # The fused kernel scatters inside its update.
                with jax.named_scope("inner/update"):
                    new_blocks.append(
                        ops.fused_block_prox_update(
                            w_blk, idx, val, coef, z_blk, eta_m,
                            lam=k_lam, lam1=k_l1, lam2=k_l2,
                        )
                    )
            else:
                with jax.named_scope("inner/scatter"):
                    g = local_scatter(idx, val, coef, block_dims[l])
                with jax.named_scope("inner/update"):
                    g = g + z_blk + reg.smooth_grad(w_blk)
                    new_blocks.append(reg.prox(w_blk - eta_m * g, eta_m))
        w_next = jnp.concatenate(new_blocks) if q > 1 else new_blocks[0]
        return w_next, None

    w_final, _ = jax.lax.scan(step, w0, (samples, step_mask))
    return w_final


# ---------------------------------------------------------------------------
# Lazy (delayed-decay) inner epoch — O(u * nnz_l) per step
# ---------------------------------------------------------------------------


def _check_lazy(lazy_updates: str | None) -> None:
    if lazy_updates not in (None, "exact", "proba"):
        raise ValueError(
            "lazy_updates must be None, 'exact', or 'proba', got "
            f"{lazy_updates!r}"
        )


def _lazy_lams(reg: losses_lib.Regularizer) -> tuple[float, float, float]:
    """Static (smooth_lam, prox_l1, prox_l2) for the lazy Pallas kernels
    and the object-level simulation helpers (whose dense counterpart,
    :func:`_sim_update`, also treats lam as static)."""
    return (reg.smooth_lam, reg.prox_l1, reg.prox_l2)


def _lazy_corrections(
    block_data: BlockCSR, n: int, u: int, lazy_updates: str | None
) -> jax.Array | None:
    """Concatenated per-feature step corrections (probabilistic variant)."""
    if lazy_updates != "proba":
        return None
    blocks = [
        ops.step_corrections(block_data.nnz_col_block(l), n, u)
        for l in range(block_data.num_blocks)
    ]
    return jnp.concatenate(blocks) if len(blocks) > 1 else blocks[0]


# Same scan skeleton as _inner_epoch, but per inner step each block does
# O(u * nnz_l) work instead of densifying all of w^(l):
#   exact —  catch up the touched features (replay their deferred steps),
#            read margins from the caught-up block, apply the dense update
#            at the touched lanes only, and reconcile every feature at
#            epoch end (lazy_flush) so the returned iterate is bit-equal
#            to _inner_epoch's;
#   proba —  touched features only, decay scaled by the per-feature
#            corrections; w is always materialized, so no counters and no
#            flush.
# Both variants read only block-local state — the all-reduced margins are
# byte-for-byte the eager schedule, so metering is unchanged by design.
#
# The smooth term is computed as ``smooth_lam * w`` with smooth_lam a
# RUNTIME scalar (lam for l2, a runtime +0.0 otherwise), never the
# compile-time ``zeros_like`` Regularizer.smooth_grad returns for the
# non-l2 modes.  With a constant-zero smooth term the replayed step's
# gradient is loop-invariant, XLA hoists the pre-rounded ``eta * g`` out
# of the replay loop, and the trajectory loses the in-loop
# ``w - eta*g`` FMA the dense scan's body gets from LLVM — a rare-input
# 1-ulp drift (see the comment block in repro/kernels/ref.py).  A runtime
# smooth_lam keeps g loop-varying; for the non-l2 modes ``smooth_lam * w``
# is ±0.0 and ``(0.0 + z) + ±0.0`` is bitwise ``0.0 + z`` (the left side
# is never -0.0), so the extra term is exact.  lam1/lam2 only enter
# through loop-invariant scalars (eta*lam1, 1 + eta*lam2) whose hoisting
# is value-preserving, so they may stay static on the kernel path.
@functools.partial(
    jax.jit,
    static_argnames=(
        "loss_name", "reg_name", "block_dims", "use_kernels", "variant",
        "lam2", "kernel_lams",
    ),
)
def _lazy_inner_epoch(
    block_indices,  # per-block int32[N, nnz_l], LOCAL ids
    block_values,  # per-block float[N, nnz_l]
    labels,
    w0,
    z_data,
    s0,
    samples,  # int32[M, u]
    eta,
    step_mask,  # float32[M]; must be a monotone prefix of ones (options I/II)
    corrections,  # [d] step corrections, or None (exact variant)
    loss_name: str,
    reg_name: str,
    lam,  # traced regularizer strength (as in _inner_epoch)
    block_dims: tuple[int, ...],
    use_kernels: bool,
    variant: str,  # "exact" | "proba"
    lam2: float = 0.0,
    kernel_lams: tuple[float, float, float] | None = None,
):
    if use_kernels and kernel_lams is None:
        raise ValueError(
            "use_kernels=True requires kernel_lams=(smooth_lam, prox_l1, "
            "prox_l2) — the lazy kernels bake them in at compile time"
        )
    loss = losses_lib.LOSSES[loss_name]
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    k_lam, k_l1, k_l2 = kernel_lams if kernel_lams else (0.0, 0.0, 0.0)
    # Runtime smooth strength: lam itself for l2, else lam * 0.0 — a traced
    # +0.0 XLA cannot fold away (see the comment above the decorator).
    smooth_lam = lam if reg_name == "l2" else lam * 0.0
    u = samples.shape[1]
    m_total = samples.shape[0]
    q = len(block_dims)
    bounds = _bounds(block_dims)
    exact = variant == "exact"
    # Number of active (unmasked) steps: option_mask yields 1s then 0s, so
    # the catch-up can decompose any gap as active replays + one masked one.
    stop = jnp.sum(step_mask).astype(jnp.int32)

    def split(vec):
        return [
            jax.lax.slice_in_dim(vec, bounds[l], bounds[l + 1])
            for l in range(q)
        ]

    def jnp_replay(wl, zl, k_active, has_masked, eta_v):
        # The untouched dense step — g is exactly the scatter's +0.0 base —
        # replayed k_active times plus at most one masked (eta_m = 0) step.
        # smooth_lam * cur (not reg.smooth_grad) keeps g loop-varying so
        # XLA can't hoist eta * g out of the loop; the value is identical.
        def one(cur, eta_i):
            g = 0.0 + zl + smooth_lam * cur
            return reg.prox(cur - eta_i * g, eta_i)

        def body(i, cur):
            return jnp.where(i < k_active, one(cur, eta_v), cur)

        wl = jax.lax.fori_loop(0, jnp.max(k_active, initial=0), body, wl)
        return jnp.where(has_masked, one(wl, eta_v * 0.0), wl)

    def jnp_catchup(w_blk, last_blk, z_blk, idx, m):
        flat = idx.reshape(-1)
        ll = last_blk[flat]
        k_active = jnp.maximum(jnp.minimum(stop, m) - ll, 0)
        has_masked = (m - ll) > k_active
        wl = jnp_replay(w_blk[flat], z_blk[flat], k_active, has_masked, eta)
        return w_blk.at[flat].set(wl), last_blk.at[flat].set(m + 1)

    def jnp_touch(w_blk, idx, val, coef, z_blk, eta_m):
        # The argmax-based first-occurrence dedup is a scalar reduce
        # XLA:CPU won't vectorize, but it is the only dedup that applies
        # the duplicate contributions in the dense scatter-add's exact
        # program order — the bit-identity contract pins it here.  The
        # proba path below, which has no bit contract, uses the fast
        # masked column-sum dedup instead.
        flat = idx.reshape(-1)
        contrib = (val * coef[..., None]).reshape(-1)
        first = ops.ref._first_occurrence(flat)
        g = jnp.zeros_like(contrib).at[first].add(contrib)
        wl = w_blk[flat]
        g = g + z_blk[flat] + smooth_lam * wl
        v = reg.prox(wl - eta_m * g, eta_m)
        return w_blk.at[flat].set(v[first])

    def jnp_flush(w_blk, last_blk, z_blk):
        total = jnp.asarray(m_total, dtype=jnp.int32)
        k_active = jnp.maximum(jnp.minimum(stop, total) - last_blk, 0)
        has_masked = (total - last_blk) > k_active
        return jnp_replay(w_blk, z_blk, k_active, has_masked, eta)

    def jnp_proba(w_blk, idx, val, coef, z_blk, corr_blk, eta_m):
        # Masked column-sum dedup: each lane of a duplicated id receives
        # the SAME summed contribution, so every duplicate computes an
        # identical v and the scatter-set below is order-independent — no
        # argmax, no first-occurrence scalar reduce.  The reduce may
        # reassociate the sum; fine here, the proba variant's contract is
        # unbiasedness, not bit order (the exact path keeps
        # _first_occurrence).
        flat = idx.reshape(-1)
        contrib = (val * coef[..., None]).reshape(-1)
        eq = flat[:, None] == flat[None, :]
        g = jnp.sum(jnp.where(eq, contrib[:, None], 0.0), axis=0)
        wl = w_blk[flat]
        cl = corr_blk[flat]
        v = wl - eta_m * (g + cl * (z_blk[flat] + smooth_lam * wl))
        if reg_name in ("l1", "elastic_net"):
            v = losses_lib.soft_threshold(v, eta_m * lam * cl)
            if lam2:
                v = v / (1.0 + eta_m * lam2 * cl)
        return w_blk.at[flat].set(v)

    z_blocks = split(z_data)
    corr_blocks = None if exact else split(corrections)

    def step(carry, inp):
        if exact:
            w, last = carry
            last_blocks = split(last)
        else:
            w = carry
        ids, mask, m = inp  # ids: int32[u]; m: int32 inner-step index
        with jax.named_scope("inner/gather"):
            y = labels[ids]
            rows = [(block_indices[l][ids], block_values[l][ids])
                    for l in range(q)]
        w_blocks = split(w)
        if exact:
            for l in range(q):
                with jax.named_scope("inner/update"):
                    if use_kernels:
                        w_blocks[l], last_blocks[l] = ops.lazy_block_catchup(
                            w_blocks[l], last_blocks[l], z_blocks[l],
                            rows[l][0], eta, m, stop,
                            lam=smooth_lam, lam1=k_l1, lam2=k_l2,
                        )
                    else:
                        w_blocks[l], last_blocks[l] = jnp_catchup(
                            w_blocks[l], last_blocks[l], z_blocks[l],
                            rows[l][0], m,
                        )
        # Margins gather only touched ids, which the catch-up just
        # materialized — so coef is bit-identical to the eager epoch's.
        with jax.named_scope("inner/gather"):
            parts = [
                block_margins(rows[l][0], rows[l][1], w_blocks[l],
                               use_kernels)
                for l in range(q)
            ]
            s_m = tree_order_sum(parts)
            s_anchor = s0[ids]
        coef = (loss.dvalue(s_m, y) - loss.dvalue(s_anchor, y)) / u
        eta_m = eta * mask
        # The lazy touch scatters its ids inside the update.
        with jax.named_scope("inner/update"):
            for l in range(q):
                idx, val = rows[l]
                if exact:
                    if use_kernels:
                        w_blocks[l] = ops.lazy_block_touch_update(
                            w_blocks[l], idx, val, coef, z_blocks[l], eta_m,
                            lam=k_lam, lam1=k_l1, lam2=k_l2,
                        )
                    else:
                        w_blocks[l] = jnp_touch(
                            w_blocks[l], idx, val, coef, z_blocks[l], eta_m
                        )
                elif use_kernels:
                    w_blocks[l] = ops.lazy_block_proba_update(
                        w_blocks[l], idx, val, coef, z_blocks[l], corr_blocks[l],
                        eta_m, lam=k_lam, lam1=k_l1, lam2=k_l2,
                    )
                else:
                    w_blocks[l] = jnp_proba(
                        w_blocks[l], idx, val, coef, z_blocks[l], corr_blocks[l],
                        eta_m,
                    )
        w_next = jnp.concatenate(w_blocks) if q > 1 else w_blocks[0]
        if exact:
            last_next = (
                jnp.concatenate(last_blocks) if q > 1 else last_blocks[0]
            )
            return (w_next, last_next), None
        return w_next, None

    steps_idx = jnp.arange(m_total, dtype=jnp.int32)
    if not exact:
        w_final, _ = jax.lax.scan(
            step, w0, (samples, step_mask, steps_idx)
        )
        return w_final
    last0 = jnp.zeros(w0.shape, dtype=jnp.int32)
    (w_final, last_final), _ = jax.lax.scan(
        step, (w0, last0), (samples, step_mask, steps_idx)
    )
    # Epoch-end flush: snapshots, objectives, and meters downstream all see
    # the fully-materialized iterate.
    w_blocks = split(w_final)
    last_blocks = split(last_final)
    total = jnp.asarray(m_total, dtype=jnp.int32)
    with jax.named_scope("inner/update"):
        for l in range(q):
            if use_kernels:
                w_blocks[l] = ops.lazy_block_flush(
                    w_blocks[l], last_blocks[l], z_blocks[l], eta, total, stop,
                    lam=smooth_lam, lam1=k_l1, lam2=k_l2,
                )
            else:
                w_blocks[l] = jnp_flush(w_blocks[l], last_blocks[l], z_blocks[l])
    return jnp.concatenate(w_blocks) if q > 1 else w_blocks[0]


# ---------------------------------------------------------------------------
# Serial SVRG (Algorithm 2)
# ---------------------------------------------------------------------------


def run_serial_svrg(
    data: PaddedCSR | None,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
    cfg: SVRGConfig,
    *,
    use_kernels: bool = False,
    block_data: BlockCSR | None = None,
    init_w: jax.Array | None = None,
    lazy_updates: str | None = None,
    recovery: RecoveryPolicy | None = None,
    checkpoint: CheckpointPolicy | None = None,
    start: Snapshot | None = None,
) -> RunResult:
    _check_lazy(lazy_updates)
    if block_data is None:
        if data is None:
            raise ValueError("pass data or a prebuilt block_data")
        # The q=1 BlockCSR shares the PaddedCSR arrays (local ids == global).
        block_data = BlockCSR.from_padded(data, balanced(data.dim, 1))
    elif block_data.num_blocks != 1:
        raise ValueError(
            f"serial SVRG runs on the q=1 layout; block_data has "
            f"{block_data.num_blocks} blocks"
        )
    # Everything below reads the block layout only — a streamed build
    # (repro.data.pipeline.stream_block_csr) runs without the global
    # PaddedCSR ever existing.  The SVRG inner step itself lives in the
    # update-rule layer now; lazy import keeps the graph acyclic
    # (repro.core.__init__ imports this module eagerly).
    from repro.optim.update_rules import SVRGRule, make_context, run_with_rule

    return run_with_rule(
        SVRGRule(use_kernels=use_kernels, lazy_updates=lazy_updates),
        make_context(block_data, loss, reg, cfg),
        init_w=init_w,
        recovery=recovery,
        checkpoint=checkpoint,
        start=start,
    )


# ---------------------------------------------------------------------------
# FD-SVRG (Algorithm 1), metered simulation
# ---------------------------------------------------------------------------


def run_fdsvrg(
    data: PaddedCSR | None,
    partition: FeaturePartition,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
    cfg: SVRGConfig,
    cluster: ClusterModel | None = None,
    backend: Collectives | None = None,
    *,
    use_kernels: bool = False,
    block_data: BlockCSR | None = None,
    init_w: jax.Array | None = None,
    lazy_updates: str | None = None,
    recovery: RecoveryPolicy | None = None,
    checkpoint: CheckpointPolicy | None = None,
    start: Snapshot | None = None,
) -> RunResult:
    """Algorithm 1 with q = partition.num_blocks feature-sharded workers.

    Numerics: identical update sequence to serial SVRG (Theorem: the
    decomposition w^T x = sum_l w^(l)T x^(l) is exact; summation follows
    the tree order), computed on the block-local
    :class:`~repro.data.block_csr.BlockCSR` layout (built once here, or
    passed in as ``block_data`` to amortize across runs — in which case
    ``data=None`` is allowed and nothing global is ever touched: the
    streamed ingestion path runs the driver from per-worker slabs alone).
    Communication/time: the paper's accounting, metered through
    ``backend`` (default: a fresh ``SimBackend``) with the shared §4.5
    closed forms (:data:`repro.dist.COSTS`) —

      outer t:  tree reduce+broadcast of the N-vector  w_t^T D  -> 2qN scalars
      inner m:  tree reduce+broadcast of u margins      -> 2qu scalars

    ``lazy_updates`` ("exact" | "proba") swaps the inner epoch for the
    delayed-decay O(u * nnz_l) path (:func:`_lazy_inner_epoch`); it is
    block-local, so the metered schedule above is unchanged bit-for-bit.

    ``start`` (the :attr:`~repro.core.driver.RunResult.final` of a run on
    this layout) starts from its iterate and snapshot instead of
    ``init_w``, computing no full gradient before the first epoch.
    """
    _check_lazy(lazy_updates)
    q = partition.num_blocks
    if backend is None:
        backend = SimBackend(q, cluster)
    elif backend.q != q:
        raise ValueError(
            f"backend has q={backend.q} workers but the partition has "
            f"{q} blocks"
        )
    if block_data is None:
        if data is None:
            raise ValueError("pass data or a prebuilt block_data")
        block_data = BlockCSR.from_padded(data, partition)
    elif block_data.partition.bounds != partition.bounds:
        raise ValueError("block_data was built for a different partition")
    # The SVRG inner step, its metering, and the default abort hook all
    # live in the update-rule layer now (lazy import: repro.core.__init__
    # imports this module eagerly, so a module-level import back into
    # repro.optim would see a partially-initialized module).
    from repro.optim.update_rules import SVRGRule, make_context, run_with_rule

    return run_with_rule(
        SVRGRule(use_kernels=use_kernels, lazy_updates=lazy_updates),
        make_context(block_data, loss, reg, cfg, backend=backend),
        init_w=init_w,
        recovery=recovery,
        checkpoint=checkpoint,
        start=start,
    )


# ---------------------------------------------------------------------------
# Explicit q-worker simulation (tests): workers only see their own blocks
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("use_kernels",))
def _sim_margins(idx, val, w_block, use_kernels):
    return block_margins(idx, val, w_block, use_kernels)


@functools.partial(jax.jit, static_argnames=("use_kernels",))
def _sim_full_margins(groups, w_block, use_kernels):
    return group_margins(groups, w_block, use_kernels)


@functools.partial(jax.jit, static_argnames=("block_dim",))
def _sim_scatter(groups, coeffs, block_dim):
    return group_scatter(groups, coeffs, block_dim)


@functools.partial(
    jax.jit, static_argnames=("reg_name", "lam", "use_kernels", "lam2")
)
def _sim_update(w_block, idx, val, coef, z_block, eta_m, reg_name, lam,
                use_kernels, lam2=0.0):
    reg = losses_lib.Regularizer(reg_name, lam, lam2)
    if use_kernels:
        return ops.fused_block_prox_update(
            w_block, idx, val, coef, z_block, eta_m,
            lam=reg.smooth_lam, lam1=reg.prox_l1, lam2=reg.prox_l2,
        )
    g = (
        local_scatter(idx, val, coef, w_block.shape[0])
        + z_block
        + reg.smooth_grad(w_block)
    )
    return reg.prox(w_block - eta_m * g, eta_m)


# Lazy per-step worker operations (object-level simulation).  ``m``/``stop``
# /``total`` arrive as traced int32 scalars so all M inner steps share one
# compilation.  The replaying pair (catchup/flush) takes the smooth
# strength ``lam`` as a traced operand — baked in, XLA hoists the replay
# loop's pre-rounded ``eta * g`` and the trajectory drifts an ulp from the
# eager per-step oracle (see repro/kernels/ref.py); the single-application
# helpers keep the full static triple like :func:`_sim_update`.
@functools.partial(jax.jit, static_argnames=("prox_lams", "use_kernels"))
def _sim_lazy_catchup(w_block, last_block, z_block, idx, eta, m, stop, lam,
                      prox_lams, use_kernels):
    lam1, lam2 = prox_lams
    fn = ops.lazy_block_catchup if use_kernels else ops.ref.lazy_catchup_ref
    return fn(w_block, last_block, z_block, idx, eta, m, stop,
              lam=lam, lam1=lam1, lam2=lam2)


@functools.partial(jax.jit, static_argnames=("lams", "use_kernels"))
def _sim_lazy_touch(w_block, idx, val, coef, z_block, eta_m, lams,
                    use_kernels):
    lam, lam1, lam2 = lams
    fn = (
        ops.lazy_block_touch_update
        if use_kernels
        else ops.ref.lazy_touch_update_ref
    )
    return fn(w_block, idx, val, coef, z_block, eta_m,
              lam=lam, lam1=lam1, lam2=lam2)


@functools.partial(jax.jit, static_argnames=("prox_lams", "use_kernels"))
def _sim_lazy_flush(w_block, last_block, z_block, eta, total, stop, lam,
                    prox_lams, use_kernels):
    lam1, lam2 = prox_lams
    fn = ops.lazy_block_flush if use_kernels else ops.ref.lazy_flush_ref
    return fn(w_block, last_block, z_block, eta, total, stop,
              lam=lam, lam1=lam1, lam2=lam2)


@functools.partial(jax.jit, static_argnames=("lams", "use_kernels"))
def _sim_lazy_proba(w_block, idx, val, coef, z_block, corr_block, eta_m,
                    lams, use_kernels):
    lam, lam1, lam2 = lams
    fn = (
        ops.lazy_block_proba_update
        if use_kernels
        else ops.ref.lazy_proba_update_ref
    )
    return fn(w_block, idx, val, coef, z_block, corr_block, eta_m,
              lam=lam, lam1=lam1, lam2=lam2)


def fdsvrg_worker_simulation(
    data: PaddedCSR | None,
    partition: FeaturePartition,
    loss: losses_lib.MarginLoss,
    reg: losses_lib.Regularizer,
    cfg: SVRGConfig,
    backend: Collectives | None = None,
    *,
    use_kernels: bool = False,
    block_data: BlockCSR | None = None,
    init_w: jax.Array | None = None,
    lazy_updates: str | None = None,
    recovery: RecoveryPolicy | None = None,
    checkpoint: CheckpointPolicy | None = None,
) -> RunResult:
    """Object-level Algorithm 1: a list of per-worker states; every
    inner-loop cross-worker scalar passes through ``backend.all_reduce``
    (default: a fresh ``SimBackend`` running the explicit Figure-5 message
    schedule), and the full-gradient tree is accounted in aggregate via
    ``meter_tree`` (its value comes from the harness snapshot — the same
    canonical tree-order sum, metered once per outer like every driver).
    Each worker holds only its block-local CSR shard and its ``w^(l)``.

    Returns a full :class:`~repro.core.driver.RunResult` (same history
    schema as every driver; the meter is the backend's).  Deliberately
    step-by-step and slow — this is the executable spec, and the vehicle
    for the backend-equivalence tests.

    ``lazy_updates`` ("exact" | "proba") runs the worker-local delayed-decay
    flow: catch up the touched features before the margin read (exact),
    update only the touched lanes, and flush each worker's block at epoch
    end — the all-reduce schedule is untouched.
    """
    _check_lazy(lazy_updates)
    q = partition.num_blocks
    backend = backend or SimBackend(q)
    if block_data is None:
        if data is None:
            raise ValueError("pass data or a prebuilt block_data")
        block_data = BlockCSR.from_padded(data, partition)
    elif block_data.partition.bounds != partition.bounds:
        raise ValueError("block_data was built for a different partition")
    labels = block_data.labels
    block_dims = block_data.block_dims
    bounds = _bounds(block_dims)
    n = block_data.num_instances

    def split(w):
        return [w[bounds[l]:bounds[l + 1]] for l in range(q)]

    def snapshot(w):
        # Lines 3-4 compute-side: per-worker partial margins, canonical
        # tree-order sum (bit-identical to every backend's all_reduce);
        # line 5: purely local scatter of the full-gradient block.
        blocks = split(w)
        partials = [
            _sim_full_margins(block_data.groups[l], blocks[l], use_kernels)
            for l in range(q)
        ]
        s0 = tree_order_sum(partials)
        coeffs0 = loss.dvalue(s0, labels) / n
        z_blocks = [
            _sim_scatter(block_data.groups[l], coeffs0, block_dims[l])
            for l in range(q)
        ]
        z_data = jnp.concatenate(z_blocks) if q > 1 else z_blocks[0]
        return z_data, s0

    lams = _lazy_lams(reg)
    smooth_lam = jnp.asarray(reg.smooth_lam, dtype=jnp.float32)
    prox_lams = (reg.prox_l1, reg.prox_l2)
    exact = lazy_updates == "exact"
    corr_blocks = (
        [
            ops.step_corrections(
                block_data.nnz_col_block(l), n, cfg.batch_size
            )
            for l in range(q)
        ]
        if lazy_updates == "proba"
        else None
    )

    def epoch(t, rng, w, z_data, s0, eta_scale=1.0):
        # Account the full-gradient tree this outer consumed (lines 3-4).
        backend.meter_tree(payload=n)
        eta_eff = cfg.eta * eta_scale  # bit-exact when eta_scale == 1
        blocks = split(w)
        z_blocks = split(z_data)
        samples = draw_samples(rng, n, cfg.inner_steps, cfg.batch_size)
        mask = option_mask(rng, cfg.inner_steps, cfg.option)
        eta_full = jnp.asarray(eta_eff, dtype=blocks[0].dtype)
        stop = jnp.asarray(int(jnp.asarray(mask).sum()), dtype=jnp.int32)
        lasts = [
            jnp.zeros((block_dims[l],), dtype=jnp.int32) for l in range(q)
        ]

        for m in range(cfg.inner_steps):
            ids = samples[m]
            rows = [
                (block_data.indices[l][ids], block_data.values[l][ids])
                for l in range(q)
            ]
            y = labels[ids]
            if exact:
                # Replay each touched feature's deferred steps so the
                # margin read below sees the materialized values.
                for l in range(q):
                    blocks[l], lasts[l] = _sim_lazy_catchup(
                        blocks[l], lasts[l], z_blocks[l], rows[l][0],
                        eta_full, jnp.asarray(m, dtype=jnp.int32), stop,
                        smooth_lam, prox_lams, use_kernels,
                    )
            # Lines 9-10: per-worker partial margins, tree-summed (u scalars).
            partial_m = [
                _sim_margins(rows[l][0], rows[l][1], blocks[l], use_kernels)
                for l in range(q)
            ]
            s_m = backend.all_reduce(partial_m, payload=cfg.batch_size)
            s_a = s0[ids]
            coef = (loss.dvalue(s_m, y) - loss.dvalue(s_a, y)) / cfg.batch_size
            eta_m = jnp.asarray(eta_eff * float(mask[m]), dtype=blocks[0].dtype)
            # Line 11: purely local prox update on each block (the prox is
            # elementwise — paper eq. 3 — so no worker needs its peers).
            for l in range(q):
                if lazy_updates is None:
                    blocks[l] = _sim_update(
                        blocks[l], rows[l][0], rows[l][1], coef, z_blocks[l],
                        eta_m, reg.name, reg.lam, use_kernels, lam2=reg.lam2,
                    )
                elif exact:
                    blocks[l] = _sim_lazy_touch(
                        blocks[l], rows[l][0], rows[l][1], coef, z_blocks[l],
                        eta_m, lams, use_kernels,
                    )
                else:
                    blocks[l] = _sim_lazy_proba(
                        blocks[l], rows[l][0], rows[l][1], coef, z_blocks[l],
                        corr_blocks[l], eta_m, lams, use_kernels,
                    )
        if exact:
            # Epoch-end reconciliation, worker-locally (zero communication).
            total = jnp.asarray(cfg.inner_steps, dtype=jnp.int32)
            for l in range(q):
                blocks[l] = _sim_lazy_flush(
                    blocks[l], lasts[l], z_blocks[l], eta_full, total, stop,
                    smooth_lam, prox_lams, use_kernels,
                )
        return jnp.concatenate(blocks) if q > 1 else blocks[0]

    return run_outer_loop(
        outer_iters=cfg.outer_iters,
        seed=cfg.seed,
        init_w=resolve_init_w(
            init_w, block_data.dim, block_data.values[0].dtype
        ),
        snapshot=snapshot,
        epoch=epoch,
        evaluate=make_same_iterate_eval(labels, loss, reg, cfg.eta),
        backend=backend,
        recovery=_with_default_abort(
            recovery, n, block_data.global_nnz_max(), q
        ),
        checkpoint=checkpoint,
    )
