"""Deployable FD-SVRG: shard_map over the mesh's feature ("model") axes.

This is the TPU-native realization of Algorithm 1, built on
:class:`repro.dist.ShardMapBackend`.  The parameter vector ``w`` lives
feature-sharded across the given mesh axes (every chip is one of the
paper's Workers); the instance data arrives in the block-local sharded
layout (:meth:`repro.data.block_csr.BlockCSR.on_mesh`): a ``[q*N, B]``
row stack of per-block re-indexed padded rows, its rows split over the
feature axes, so each worker holds only its own block's entries with
LOCAL feature ids and ``B ≈ nnz_max / q``.  That is the paper's
construction verbatim — worker l stores the feature *slice* of every
instance.  The full gradient reads each block's rows grouped by length
class (:class:`repro.data.block_csr.RowGroups`), which have one shape on
every chip; the inner epoch samples rows of the slab.

Communication per inner step is exactly one all-reduce of ``u`` scalars
over the feature axes — the hardware tree standing in for Figure 5.  The
full-gradient phase all-reduces the N-vector of margins once per outer
iteration.  Everything else is chip-local.  The collective is selected by
the backend's ``tree_mode``:

  * ``"psum"``      — hardware all-reduce (default, fastest)
  * ``"butterfly"`` — explicit log-depth ppermute butterfly
    (:func:`repro.dist.tree.collective_permute_tree`) proving the
    paper's explicit topology lowers on TPU; used in §Perf comparisons.

``use_kernels=True`` routes the chip-local margin and scatter+update
through the fused Pallas kernels (:mod:`repro.kernels`), interpret-mode
off-TPU; ``False`` is the jnp numerics oracle — bit-identical in
interpret mode.  Nothing runs Pallas inside shard_map on a chip: one CPU
test checks it in interpret mode on a one-device mesh, and ``solve()``
does not offer it.

Two granularities of compiled step:

* :func:`make_fullgrad` + :func:`make_inner_epoch` — the snapshot and
  epoch halves :func:`run_fdsvrg_sharded` plugs into the shared
  outer-loop harness (:func:`repro.core.driver.run_outer_loop`), so the
  deployable path reports the same :class:`~repro.core.driver.RunResult`
  schema — objective, same-iterate optimality residual, metered scalars,
  modeled time — as every other driver, in the data's dtype.
* :func:`make_outer_iteration` — both phases fused into one jittable
  call (the AOT shape ``launch/dryrun`` compiles).

On-device traffic cannot be observed from traced code, so
:func:`run_fdsvrg_sharded` meters host-side through the backend with the
shared §4.5 closed forms (:data:`repro.dist.COSTS`) — the same
accounting, the same meter, and therefore the same modeled time as the
simulation driver (asserted in tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import losses as losses_lib
from repro.core.driver import (
    Snapshot,
    draw_samples,
    make_same_iterate_eval,
    resolve_init_w,
    run_outer_loop,
)
from repro.core.partition import FeaturePartition, balanced
from repro.data.block_csr import (
    BlockCSR,
    RowGroups,
    block_margins,
    group_margins,
    group_scatter,
    local_scatter,
)
from repro.dist import COSTS, ClusterModel, ShardMapBackend
from repro.kernels import ops


def _opt_residual_blk(reg, eta, w_blk, z_blk):
    """Block-local optimality residual: the gradient for smooth g, the
    prox gradient mapping otherwise (the per-block body of
    repro.core.driver.optimality_norm; callers psum the squares).  Only
    the fused AOT step reports it — the harness driver evaluates
    host-side like everyone else."""
    if reg.is_smooth:
        return z_blk + reg.grad(w_blk)
    v_blk = reg.prox(w_blk - eta * (z_blk + reg.smooth_grad(w_blk)), eta)
    return (w_blk - v_blk) / eta


@dataclasses.dataclass(frozen=True)
class FDSVRGShardedConfig:
    dim: int
    num_instances: int
    nnz_max: int  # nnz budget of the GLOBAL rows (metering uses this)
    eta: float
    inner_steps: int
    batch_size: int = 16
    loss_name: str = "logistic"
    reg_name: str = "l2"  # "l2" | "l1" | "elastic_net" | "none"
    lam: float = 1e-4
    lam2: float = 0.0  # elastic-net L2 strength
    tree_mode: str = "psum"  # or "butterfly"
    use_kernels: bool = False


def _resolve_backend(
    mesh: Mesh,
    cfg: FDSVRGShardedConfig,
    feature_axes: Sequence[str],
    backend: ShardMapBackend | None,
) -> tuple[ShardMapBackend, int]:
    """Shared builder plumbing: backend/mesh consistency + block size."""
    if backend is None:
        backend = ShardMapBackend(
            mesh=mesh, feature_axes=feature_axes, tree_mode=cfg.tree_mode
        )
    elif backend.mesh is not mesh or backend.feature_axes != tuple(feature_axes):
        raise ValueError(
            "backend was built on a different mesh/feature_axes than the ones "
            "passed to the step builder"
        )
    q = backend.q
    if cfg.dim % q != 0:
        raise ValueError(
            f"dim {cfg.dim} must divide by q={q}: the step builders take "
            "an evenly sharded feature axis (run_fdsvrg_sharded pads it)"
        )
    return backend, cfg.dim // q


def _fullgrad_blk(cfg, backend, loss, block, w_blk, groups: RowGroups, labels):
    """Full-gradient phase on one worker (Alg 1 lines 3-5) over its
    block's row groups: one N-vector all-reduce of the partial margins,
    then a purely block-local scatter."""
    with jax.named_scope("full_grad/margins"):
        partial = group_margins(groups, w_blk, cfg.use_kernels)
    with jax.named_scope("full_grad/reduce"):
        s0 = backend.device_all_reduce(partial)
    with jax.named_scope("full_grad/scatter"):
        coeffs = loss.dvalue(s0, labels) / labels.shape[0]
        z_blk = group_scatter(groups, coeffs, block)
    return z_blk, s0


def _slab_groups(bidx, bval) -> RowGroups:
    """A worker's slab as its one row group (every row in one class)."""
    ident = jnp.arange(bidx.shape[0], dtype=jnp.int32)
    return RowGroups((bidx,), (bval,), (ident,), ident)


def _groups_spec(groups: RowGroups, axes) -> RowGroups:
    """shard_map specs of a mesh-placed RowGroups: each group's rows
    split over the feature axes, ``rows`` and ``order`` replicated."""
    return RowGroups(
        indices=tuple(P(axes, None) for _ in groups.indices),
        values=tuple(P(axes, None) for _ in groups.values),
        rows=tuple(P() for _ in groups.rows),
        order=P(),
    )


def _inner_scan_blk(cfg, backend, loss, reg, block,
                    w_blk, z_blk, s0, bidx, bval, labels, samples):
    """M inner steps on one worker: one u-scalar all-reduce per step; the
    prox is elementwise on the local block, so the traffic is identical
    for every regularizer."""

    def step(w_b, ids):
        with jax.named_scope("inner/gather"):
            idx = bidx[ids]
            val = bval[ids]
            y = labels[ids]
            partial = block_margins(idx, val, w_b, cfg.use_kernels)
        # The per-step all-reduce of u partial margins.
        with jax.named_scope("inner/reduce"):
            s_m = backend.device_all_reduce(partial)
        coef = (loss.dvalue(s_m, y) - loss.dvalue(s0[ids], y)) / cfg.batch_size
        if cfg.use_kernels:
            with jax.named_scope("inner/update"):
                w_next = ops.fused_block_prox_update(
                    w_b, idx, val, coef, z_blk, cfg.eta,
                    lam=reg.smooth_lam, lam1=reg.prox_l1, lam2=reg.prox_l2,
                )
        else:
            with jax.named_scope("inner/scatter"):
                g = local_scatter(idx, val, coef, block)
            with jax.named_scope("inner/update"):
                g = g + z_blk + reg.smooth_grad(w_b)
                w_next = reg.prox(w_b - cfg.eta * g, cfg.eta)
        return w_next, None

    w_blk, _ = jax.lax.scan(step, w_blk, samples)
    return w_blk


def make_fullgrad(
    mesh: Mesh,
    cfg: FDSVRGShardedConfig,
    feature_axes: Sequence[str] = ("data", "model"),
    backend: ShardMapBackend | None = None,
):
    """Build the jittable snapshot half: ``(w, groups, labels) -> (z,
    s0)`` with ``groups`` the mesh-placed row groups
    (:meth:`BlockCSR.on_mesh`), ``z`` feature-sharded like ``w`` and
    ``s0`` (the margins at ``w``) replicated.  This is the harness
    ``snapshot`` hook — its output rotates into the next epoch AND
    carries the same-iterate reporting pair.  One program is traced per
    group structure."""
    backend, block = _resolve_backend(mesh, cfg, feature_axes, backend)
    loss = losses_lib.LOSSES[cfg.loss_name]
    axes = backend.feature_axes

    def worker(w_blk, groups, labels):
        return _fullgrad_blk(cfg, backend, loss, block, w_blk, groups, labels)

    # The program's name (XLA module ``jit_mesh_full_grad``) is what
    # the benchmark's trace readers look for.
    @jax.jit
    def mesh_full_grad(w, groups, labels):
        mapped = backend.shard_map(
            worker,
            in_specs=(P(axes), _groups_spec(groups, axes), P(None)),
            out_specs=(P(axes), P(None)),
        )
        return mapped(w, groups, labels)

    return mesh_full_grad


def make_inner_epoch(
    mesh: Mesh,
    cfg: FDSVRGShardedConfig,
    feature_axes: Sequence[str] = ("data", "model"),
    backend: ShardMapBackend | None = None,
):
    """Build the jittable epoch half: ``(w, z, s0, block_indices,
    block_values, labels, samples) -> w_next`` — the M-step inner scan
    consuming a snapshot produced by :func:`make_fullgrad`, over the
    ``[q*N, B]`` row-stacked slabs."""
    backend, block = _resolve_backend(mesh, cfg, feature_axes, backend)
    loss = losses_lib.LOSSES[cfg.loss_name]
    reg = losses_lib.Regularizer(cfg.reg_name, cfg.lam, cfg.lam2)
    axes = backend.feature_axes

    def worker(w_blk, z_blk, s0, bidx, bval, labels, samples):
        return _inner_scan_blk(
            cfg, backend, loss, reg, block,
            w_blk, z_blk, s0, bidx, bval, labels, samples,
        )

    spec_rows = P(axes, None)
    mapped = backend.shard_map(
        worker,
        in_specs=(P(axes), P(axes), P(None), spec_rows, spec_rows,
                  P(None), P(None, None)),
        out_specs=P(axes),
    )

    # XLA module ``jit_mesh_inner_epoch``, as the trace readers know it.
    @jax.jit
    def mesh_inner_epoch(w, z, s0, bidx, bval, labels, samples):
        return mapped(w, z, s0, bidx, bval, labels, samples)

    return mesh_inner_epoch


def make_outer_iteration(
    mesh: Mesh,
    cfg: FDSVRGShardedConfig,
    feature_axes: Sequence[str] = ("data", "model"),
    backend: ShardMapBackend | None = None,
):
    """Build the fused one-outer-iteration function (the AOT shape).

    Signature of the returned fn:
      (w, block_indices, block_values, labels, samples)
        -> (w_next, full_grad_norm)
    with shardings:
      w:             P(feature_axes)        (feature-distributed, the paper)
      block_indices: P(feature_axes, None)  int32[q*N, B] local ids
      block_values:  P(feature_axes, None)  float[q*N, B]
      labels:        P(None)
      samples:       P(None, None)          int32[M, u]

    ``full_grad_norm`` is the optimality residual at the *snapshot*
    iterate (the full-gradient phase computes it for free); the harness
    driver (:func:`run_fdsvrg_sharded`) reports post-epoch residuals
    instead, via the split :func:`make_fullgrad` / :func:`make_inner_epoch`
    pair.  Its full gradient reads the slab as one row group (the AOT
    shape of uniform rows).  Build the data stack once with
    ``BlockCSR.from_padded(data, balanced(dim, q)).stacked()``.
    """
    backend, block = _resolve_backend(mesh, cfg, feature_axes, backend)
    loss = losses_lib.LOSSES[cfg.loss_name]
    reg = losses_lib.Regularizer(cfg.reg_name, cfg.lam, cfg.lam2)
    axes = backend.feature_axes

    def worker(w_blk, bidx, bval, labels, samples):
        z_blk, s0 = _fullgrad_blk(
            cfg, backend, loss, block, w_blk, _slab_groups(bidx, bval), labels
        )
        gnorm_sq = jax.lax.psum(
            jnp.sum(_opt_residual_blk(reg, cfg.eta, w_blk, z_blk) ** 2), axes
        )
        w_blk = _inner_scan_blk(
            cfg, backend, loss, reg, block,
            w_blk, z_blk, s0, bidx, bval, labels, samples,
        )
        return w_blk, gnorm_sq

    spec_w = P(axes)
    spec_rows = P(axes, None)
    mapped = backend.shard_map(
        worker,
        in_specs=(spec_w, spec_rows, spec_rows, P(None), P(None, None)),
        out_specs=(spec_w, P()),
    )

    @jax.jit
    def outer_iteration(w, block_indices, block_values, labels, samples):
        w_next, gnorm_sq = mapped(w, block_indices, block_values, labels, samples)
        return w_next, jnp.sqrt(gnorm_sq)

    return outer_iteration


def mesh_partition(dim: int, q: int) -> FeaturePartition:
    """The mesh's partition of ``dim`` features: q blocks of one size,
    ``dim`` padded with zero columns up to the next multiple of q."""
    return balanced(-(-dim // q) * q, q)


@functools.lru_cache(maxsize=8)
def _steps(mesh: Mesh, cfg: FDSVRGShardedConfig, axes: tuple[str, ...]):
    """The compiled full-gradient and inner-epoch halves for one mesh
    and configuration, built once: a warm-started ``solve()`` call with
    the same shapes traces nothing again."""
    backend = ShardMapBackend(mesh=mesh, feature_axes=axes,
                              tree_mode=cfg.tree_mode)
    return (make_fullgrad(mesh, cfg, axes, backend=backend),
            make_inner_epoch(mesh, cfg, axes, backend=backend))


def run_fdsvrg_sharded(
    data,
    mesh: Mesh,
    cfg: FDSVRGShardedConfig,
    feature_axes: Sequence[str] = ("data", "model"),
    outer_iters: int = 1,
    seed: int = 0,
    cluster: ClusterModel | None = None,
    backend: ShardMapBackend | None = None,
    init_w: jax.Array | None = None,
    start: Snapshot | None = None,
):
    """Metered driver for the deployable path, on the shared harness.

    ``data`` is a :class:`~repro.data.block_csr.BlockCSR` on
    :func:`mesh_partition` (``BlockCSR.from_blocks`` of blocks made one
    per device, or ``from_padded``), or a PaddedCSR, which is
    re-indexed here.  The layout is placed on the mesh once
    (:meth:`BlockCSR.on_mesh`, kept with the layout) and runs
    ``outer_iters`` iterations of the split :func:`make_fullgrad` /
    :func:`make_inner_epoch` pair through
    :func:`repro.core.driver.run_outer_loop` — so snapshot rotation,
    sample drawing (same rng stream as :func:`repro.core.fdsvrg.run_fdsvrg`
    at the same seed), and same-iterate objective/optimality reporting
    are the engine's, not a local copy.  Traffic and modeled time are
    charged from the shared closed forms (:data:`repro.dist.COSTS`), so
    the meter is bit-consistent with the simulation driver's for the same
    shapes (asserted in tests).  Under the profiler each full-gradient
    dispatch adds the groups' lanes over all chips and the stored
    entries to ``full_grad.lanes`` / ``full_grad.stored``, and each
    epoch its M all-reduces to ``mesh.allreduce_steps``
    (:mod:`repro.obs`).

    A ``dim`` that q does not divide is padded with zero feature
    columns up to the next multiple of q.  No row stores them, so their
    data gradient is zero and their weights stay at their zero start: the
    objective, the residual and the real coordinates are those of the
    unpadded problem, and the pad is sliced off the returned ``w``.

    Returns a :class:`~repro.core.driver.RunResult` — same schema as
    every other driver, iterates in the data's dtype.  Its ``w`` is
    replicated over the mesh, so host-side code may index it whatever
    the mesh's axis types (a gather on an Explicit-sharded array needs
    an output sharding the caller has no reason to know).  Its ``final``
    holds the padded, feature-sharded iterate and the snapshot at it;
    passed back as ``start``, a call on the same layout and mesh starts
    there, with no full gradient and no re-placement of the iterate
    before its first epoch (``init_w`` is then ignored).
    """
    backend = backend or ShardMapBackend(
        mesh=mesh, feature_axes=feature_axes,
        tree_mode=cfg.tree_mode, cluster=cluster,
    )
    q = backend.q
    dim = cfg.dim
    partition = mesh_partition(dim, q)
    if isinstance(data, BlockCSR):
        if data.partition != partition or data.dim != dim:
            raise ValueError(
                f"the layout covers dim={data.dim} in blocks "
                f"{data.partition.bounds}; the mesh of {q} needs dim={dim} "
                f"in {partition.bounds}"
            )
        block_data = data
    else:
        block_data = BlockCSR.from_padded(data, partition)
    cfg = dataclasses.replace(cfg, dim=partition.dim)
    if start is not None:
        if start.w.shape != (partition.dim,):
            raise ValueError(
                f"start holds an iterate of shape {start.w.shape}; the mesh "
                f"layout runs ({partition.dim},)"
            )
        w0 = start.w
    else:
        # One placement whatever the caller's, so that a call
        # warm-started from a returned (replicated) iterate runs the
        # programs a cold one compiled, and traces nothing.
        w0 = jax.device_put(
            resolve_init_w(init_w, dim, block_data.values[0].dtype),
            NamedSharding(mesh, P()),
        )
        if partition.dim != dim:
            w0 = jnp.pad(w0, (0, partition.dim - dim))
        w0 = jax.device_put(w0, NamedSharding(mesh, P(tuple(feature_axes))))
    _resolve_backend(mesh, cfg, feature_axes, backend)
    fullgrad, inner_epoch = _steps(
        mesh, dataclasses.replace(cfg, tree_mode=backend.tree_mode),
        backend.feature_axes,
    )
    placed = block_data.on_mesh(mesh, backend.feature_axes)
    bidx, bval, labels = placed.indices, placed.values, placed.labels
    lanes = placed.groups.lanes
    loss = losses_lib.LOSSES[cfg.loss_name]
    reg = losses_lib.Regularizer(cfg.reg_name, cfg.lam, cfg.lam2)
    n, nnz, u = cfg.num_instances, cfg.nnz_max, cfg.batch_size

    def snapshot(w):
        obs.count("full_grad.lanes", lanes)
        obs.count("full_grad.stored", block_data.stored)
        return fullgrad(w, placed.groups, labels)

    def epoch(t, rng, w, z_data, s0):
        backend.meter_tree(payload=n)
        backend.charge_cost(COSTS.fd_fullgrad(n=n, nnz=nnz, q=q))
        with obs.span("outer.samples"):
            samples = jnp.asarray(draw_samples(rng, n, cfg.inner_steps, u))
        with obs.span("outer.epoch"):
            obs.count("mesh.allreduce_steps", cfg.inner_steps)
            w = inner_epoch(w, z_data, s0, bidx, bval, labels, samples)
        backend.meter_tree(payload=u, steps=cfg.inner_steps)
        backend.charge_cost(
            COSTS.fd_inner_step(nnz=nnz, q=q, u=u), steps=cfg.inner_steps
        )
        return w

    result = run_outer_loop(
        outer_iters=outer_iters,
        seed=seed,
        init_w=w0,
        snapshot=snapshot,
        epoch=epoch,
        evaluate=make_same_iterate_eval(labels, loss, reg, cfg.eta),
        backend=backend,
        init_snapshot=None if start is None else (start.z, start.s0),
    )
    w = jax.device_put(result.w, NamedSharding(mesh, P()))
    return dataclasses.replace(result, w=w[:dim])


def input_shardings(mesh: Mesh, feature_axes: Sequence[str] = ("data", "model")):
    axes = tuple(feature_axes)
    return (
        NamedSharding(mesh, P(axes)),
        NamedSharding(mesh, P(axes, None)),
        NamedSharding(mesh, P(axes, None)),
        NamedSharding(mesh, P(None)),
        NamedSharding(mesh, P(None, None)),
    )
