"""Block-local sharded CSR: the feature-distributed layout of a PaddedCSR.

The masked global-CSR view of a feature shard keeps *global* padded rows
``(indices, values)`` on every worker and masks per-block membership on
every access — ``(idx >= lo) & (idx < hi)`` plus a ``where``-guarded
gather, O(nnz_max) work per worker per row regardless of q.  That defeats
the paper's whole point: worker l's compute should shrink with the number
of workers.

``BlockCSR`` re-indexes once, at load time.  For each feature block l of a
:class:`~repro.core.partition.FeaturePartition` it stores the block's
entries of every instance as padded rows with a *per-block* nnz budget:

    indices[l]: int32[N, nnz_l]   LOCAL feature ids in [0, dim_l), pad 0
    values[l]:  float[N, nnz_l]   matching values, pad 0.0

so worker l gathers against its local dense ``w`` block with zero masking
arithmetic — the hot-path cost is O(nnz_l) ≈ O(nnz_max / q).  Padding with
(local id 0, value 0.0) is safe for every operation here (dots and
scatter-adds): a zero value contributes nothing.

Entry order within a row is preserved from the source PaddedCSR, so
per-feature scatter accumulation order — and therefore floating point —
matches the global layout.

:func:`local_margins` / :func:`local_scatter` are the two block-local hot
paths; they are also the numerics contract for the fused Pallas kernels in
:mod:`repro.kernels` (``sparse_margin``, ``fused_update``).

Each block also carries its rows grouped by stored length
(:class:`RowGroups`): the full gradient walks every row, so it reads
the groups, each padded only to its own multiple of 128 lanes, and not
the ``[N, nnz_l]`` slab.  The inner epoch samples rows by id and keeps
reading the slab.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import PaddedCSR

if TYPE_CHECKING:  # import would cycle through repro.core.__init__ at runtime
    from repro.core.partition import FeaturePartition


#: Row groups pad to a multiple of this many lanes (a TPU vector
#: register's width).
GROUP_LANES = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowGroups:
    """One block's rows grouped by length class, for the full gradient.

    A row's length is one past its last stored (nonzero) entry; its
    class is that length rounded up to a multiple of :data:`GROUP_LANES`,
    at least one multiple, at most the slab's width.  Rows are ordered
    stably by class, so row order holds inside a group.  Group b keeps
    the first ``W_b`` (its class) lanes of its rows, which hold every
    stored entry of them.  When every row falls in one class whose
    width is the slab's, the one group *is* the slab, with the identity
    order.
    """

    indices: tuple[jax.Array, ...]  # per group: int32[N_b, W_b], local ids
    values: tuple[jax.Array, ...]  # per group: float[N_b, W_b]
    rows: tuple[jax.Array, ...]  # per group: int32[N_b], source row ids
    # int32[N]: source row i sits at position order[i] of the groups
    # concatenated, so concat(per-group margins)[order] is in row order.
    order: jax.Array

    @property
    def lanes(self) -> int:
        """Lanes one pass over the groups touches: sum of N_b * W_b."""
        return sum(int(i.size) for i in self.indices)


def row_groups(
    indices: np.ndarray,
    values: np.ndarray,
    slab: tuple[jax.Array, jax.Array],
) -> RowGroups:
    """Group one block's ``[N, W]`` rows by length class (host-side).

    ``indices`` / ``values`` are the host copy of the slab and ``slab``
    the layout's own arrays, which become the one group when all rows
    fall in one class as wide as the slab.
    """
    n, width = values.shape
    stored = values != 0.0
    length = np.where(
        stored.any(axis=1), width - np.argmax(stored[:, ::-1], axis=1), 0
    )
    lanes = np.maximum(-(-length // GROUP_LANES), 1) * GROUP_LANES
    cls = np.minimum(lanes, width)
    widths = np.unique(cls)
    if widths.size <= 1:
        w_b = int(widths[0]) if widths.size else width
        if w_b == width:
            idx_b, val_b = slab
        else:
            idx_b = jnp.asarray(indices[:, :w_b])
            val_b = jnp.asarray(values[:, :w_b])
        ident = jnp.arange(n, dtype=jnp.int32)
        return RowGroups((idx_b,), (val_b,), (ident,), ident)
    perm = np.argsort(cls, kind="stable").astype(np.int32)
    order = np.empty(n, np.int32)
    order[perm] = np.arange(n, dtype=np.int32)
    starts = np.searchsorted(cls[perm], widths)
    ends = np.append(starts[1:], n)
    grouped = [
        (perm[a:b], int(w_b)) for a, b, w_b in zip(starts, ends, widths)
    ]
    return RowGroups(
        indices=tuple(jnp.asarray(indices[r, :w_b]) for r, w_b in grouped),
        values=tuple(jnp.asarray(values[r, :w_b]) for r, w_b in grouped),
        rows=tuple(jnp.asarray(r) for r, _ in grouped),
        order=jnp.asarray(order),
    )


@dataclasses.dataclass(frozen=True)
class BlockCSR:
    """A PaddedCSR re-indexed into q block-local shards."""

    partition: FeaturePartition
    indices: tuple[jax.Array, ...]  # per block: int32[N, nnz_l], local ids
    values: tuple[jax.Array, ...]  # per block: float[N, nnz_l]
    labels: jax.Array  # float[N], in {-1, +1}
    dim: int  # global d
    # Per-block column-nnz statistics: int32[dim_l] counting, for each
    # LOCAL feature id, the number of instances whose rows store it with a
    # nonzero value (explicit zeros were dropped by from_padded, so these
    # are structural-nonzero counts of the layout as stored).  They feed
    # the probabilistic lazy-update step corrections N/nnz_col(j) — see
    # repro.kernels.lazy_update.  None means "not computed" (direct
    # constructions); use nnz_col_block() which computes on demand.
    nnz_col: tuple[jax.Array, ...] | None = None
    # The source's global padded-row width (PaddedCSR.nnz_max).  The
    # drivers charge per-instance communication/compute cost against it,
    # so carrying it here lets a run start from slabs alone — no global
    # PaddedCSR in memory.  None on direct constructions that predate the
    # streaming path; use global_nnz_max() which falls back to the sum of
    # per-block budgets (exact when budgets are tight and rows dense).
    nnz_max: int | None = None
    # Stored entries (nonzero values) over all blocks, counted on the
    # host when the layout is built; direct constructions that leave it
    # None count it once here.
    stored: int | None = None
    # Per block: its rows grouped by length class (row_groups), which
    # the full gradient reads.  Built with the layout; direct
    # constructions that leave it None build it once here.
    groups: tuple[RowGroups, ...] | None = None

    def __post_init__(self) -> None:
        if self.stored is not None and self.groups is not None:
            return
        host = [(np.asarray(i), np.asarray(v))
                for i, v in zip(self.indices, self.values)]
        if self.stored is None:
            object.__setattr__(self, "stored", sum(
                int(np.count_nonzero(v)) for _, v in host))
        if self.groups is None:
            object.__setattr__(self, "groups", tuple(
                row_groups(i, v, slab)
                for (i, v), slab in zip(host, zip(self.indices, self.values))))

    @property
    def num_blocks(self) -> int:
        return self.partition.num_blocks

    @property
    def num_instances(self) -> int:
        return int(self.indices[0].shape[0])

    @property
    def block_dims(self) -> tuple[int, ...]:
        return tuple(self.partition.block_sizes())

    @property
    def nnz_budgets(self) -> tuple[int, ...]:
        return tuple(int(i.shape[1]) for i in self.indices)

    def block(self, l: int) -> tuple[jax.Array, jax.Array]:
        return self.indices[l], self.values[l]

    def global_nnz_max(self) -> int:
        """The global padded-row width the cost model charges against.

        Exact when set by the constructor (``from_padded`` /
        ``stream_block_csr``); otherwise a conservative reconstruction
        from the per-block budgets (their sum bounds the widest global
        row from above).
        """
        if self.nnz_max is not None:
            return self.nnz_max
        return int(sum(self.nnz_budgets))

    def nnz_col_block(self, l: int) -> jax.Array:
        """int32[dim_l] per-feature instance counts for block ``l``.

        Counts rows storing a *nonzero* value at each local id, so padding
        and explicit zeros (which the scatter/gather paths cannot
        distinguish — see the explicit-zero invariant on
        :meth:`from_padded`) contribute nothing.  Precomputed by
        :meth:`from_padded`; computed on demand for directly-constructed
        instances (host-side numpy, cheap relative to re-indexing).
        """
        if self.nnz_col is not None:
            return self.nnz_col[l]
        return jnp.asarray(
            _count_cols(
                np.asarray(self.indices[l]),
                np.asarray(self.values[l]),
                int(self.block_dims[l]),
            )
        )

    @classmethod
    def from_padded(
        cls,
        data: PaddedCSR,
        partition: FeaturePartition,
        *,
        lane_multiple: int = 1,
    ) -> "BlockCSR":
        """Build the block-local layout (host-side, once per data set).

        ``lane_multiple`` rounds each block's nnz budget up (TPU lane
        padding); 1 keeps the budgets tight, which the equivalence tests
        use.  The single-block partition reuses the PaddedCSR rows as-is
        (local ids == global ids when lo = 0), so the q = 1 path is
        bit-for-bit the global layout.

        **Explicit-zero invariant.**  Entries with ``value == 0.0`` are
        dropped during re-indexing (the ``val != 0.0`` filter below), so
        an explicitly stored zero becomes indistinguishable from padding —
        including the collision case where a genuine ``(global id lo,
        0.0)`` entry would land exactly on the padding pattern ``(local
        id 0, value 0.0)``.  This is safe for every operation this layout
        supports — dots (:func:`local_margins`) and scatter-adds
        (:func:`local_scatter`) — because a zero *value* contributes
        nothing regardless of its index; the property tests in
        ``tests/test_block_csr.py`` pin margins/scatter equality against
        the masked oracle on data containing explicit zeros.  Any future
        operation that keys off *structural* nonzeros (e.g. counting
        stored entries per feature) must not assume explicit zeros
        survive this constructor.
        """
        if partition.dim != data.dim:
            raise ValueError(
                f"partition covers dim={partition.dim}, data has dim={data.dim}"
            )
        if partition.num_blocks == 1:
            idx, val = np.asarray(data.indices), np.asarray(data.values)
            nnz_col = _count_cols(idx, val, data.dim)
            return cls(
                partition=partition,
                indices=(data.indices,),
                values=(data.values,),
                labels=data.labels,
                dim=data.dim,
                nnz_col=(jnp.asarray(nnz_col),),
                nnz_max=data.nnz_max,
                stored=int(nnz_col.sum()),
                groups=(row_groups(idx, val, (data.indices, data.values)),),
            )
        idx = np.asarray(data.indices)
        val = np.asarray(data.values)
        n = idx.shape[0]
        block_indices: list[jax.Array] = []
        block_values: list[jax.Array] = []
        block_nnz_col: list[jax.Array] = []
        block_groups: list[RowGroups] = []
        stored = 0
        for l in range(partition.num_blocks):
            lo, hi = partition.block(l)
            in_blk = (idx >= lo) & (idx < hi) & (val != 0.0)
            counts = in_blk.sum(axis=1)
            budget = max(1, int(counts.max()) if n else 1)
            budget += (-budget) % lane_multiple
            out_idx = np.zeros((n, budget), dtype=np.int32)
            out_val = np.zeros((n, budget), dtype=val.dtype)
            rows, cols = np.nonzero(in_blk)  # row-major: preserves row order
            # position of each entry within its (compacted) row
            pos = np.arange(rows.size) - np.searchsorted(rows, rows, side="left")
            out_idx[rows, pos] = idx[rows, cols] - lo
            out_val[rows, pos] = val[rows, cols]
            block_indices.append(jnp.asarray(out_idx))
            block_values.append(jnp.asarray(out_val))
            block_groups.append(row_groups(
                out_idx, out_val, (block_indices[-1], block_values[-1])))
            nnz_col = _count_cols(out_idx, out_val, hi - lo)
            block_nnz_col.append(jnp.asarray(nnz_col))
            stored += int(nnz_col.sum())
        return cls(
            partition=partition,
            indices=tuple(block_indices),
            values=tuple(block_values),
            labels=data.labels,
            dim=data.dim,
            nnz_col=tuple(block_nnz_col),
            nnz_max=data.nnz_max,
            stored=stored,
            groups=tuple(block_groups),
        )

    def stacked(self, budget: int | None = None) -> tuple[jax.Array, jax.Array]:
        """Uniform-budget [q, N, B] index/value stacks for ``shard_map``.

        shard_map shards need identical shapes per worker, so every block
        is padded up to a common nnz budget (default: the max per-block
        budget).  Shard the leading axis over the feature mesh axes and
        each worker receives only its O(nnz_max/q)-wide local rows.
        """
        common = max(self.nnz_budgets)
        if budget is not None:
            if budget < common:
                raise ValueError(f"budget {budget} < required {common}")
            common = budget
        idx = jnp.stack(
            [
                jnp.pad(i, ((0, 0), (0, common - i.shape[1])))
                for i in self.indices
            ]
        )
        val = jnp.stack(
            [
                jnp.pad(v, ((0, 0), (0, common - v.shape[1])))
                for v in self.values
            ]
        )
        return idx, val

    def nnz_total(self) -> int:
        return self.stored


def _count_cols(indices: np.ndarray, values: np.ndarray, dim: int) -> np.ndarray:
    """int32[dim] count of rows storing a nonzero value per local id."""
    mask = values != 0.0
    return np.bincount(
        indices[mask].reshape(-1), minlength=dim
    ).astype(np.int32)


def aot_nnz_budget(nnz_max: int, q: int) -> int:
    """Stacked-layout nnz budget for AOT (dry-run / perf) shapes.

    The runtime budget is data-dependent (``BlockCSR.stacked``); for
    compile-only shapes we model nnz_max/q with 4x slack for skewed text
    feature popularity, never below one lane octet.  Keep in lockstep
    with what ``run_fdsvrg_sharded`` feeds the compiled step.
    """
    return max(8, -(-nnz_max // q) * 4)


def local_margins(
    indices: jax.Array, values: jax.Array, w_block: jax.Array
) -> jax.Array:
    """s^(l)_i = w^(l)T x^(l)_i from block-LOCAL padded rows.

    No membership mask, no id arithmetic: ``indices`` are already local to
    ``w_block``.  Works on [N, nnz_l] (full data) and [u, nnz_l] (sampled
    rows) alike.
    """
    return jnp.sum(w_block[indices] * values, axis=-1)


def local_scatter(
    indices: jax.Array,
    values: jax.Array,
    coeffs: jax.Array,
    block_dim: int,
) -> jax.Array:
    """sum_i coeffs_i * x^(l)_i as a dense block vector, local ids only."""
    flat_idx = indices.reshape(-1)
    flat_val = (values * coeffs[..., None]).reshape(-1)
    return jnp.zeros((block_dim,), dtype=values.dtype).at[flat_idx].add(flat_val)
