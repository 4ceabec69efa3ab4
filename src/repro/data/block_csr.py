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
:func:`block_prox_update` is the one inner-step block update every FD
driver calls, jnp or fused kernel.

Each block also carries its rows grouped by stored length
(:class:`RowGroups`): the full gradient walks every row, so it reads
the groups, each padded only to its own multiple of 128 lanes, and not
the ``[N, nnz_l]`` slab.  A row's class is set by its longest block
share, so every block orders and groups its rows alike (one ``rows``
and ``order`` for all blocks, which a mesh replicates).  The inner
epoch samples rows by id and keeps reading the slab.

A layout is built either from one global PaddedCSR on the host
(:meth:`BlockCSR.from_padded`) or from blocks that already sit one per
device (:meth:`BlockCSR.from_blocks`), whose groups are made on each
block's own device; :meth:`BlockCSR.on_mesh` hands either to a mesh,
without a copy where each block already sits on the device the mesh
gives it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.data.sparse import PaddedCSR
from repro.kernels import ops

if TYPE_CHECKING:  # import would cycle through repro.core.__init__ at runtime
    from repro.core.partition import FeaturePartition


#: Row groups pad to a multiple of this many lanes (a TPU vector
#: register's width).
GROUP_LANES = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowGroups:
    """One block's rows grouped by length class, for the full gradient.

    A row's length in a block is one past its last stored (nonzero)
    entry there; its class is its longest length over the layout's
    blocks rounded up to a multiple of :data:`GROUP_LANES`, at least one
    multiple, at most the widest block.  Rows are ordered stably by
    class, so row order holds inside a group and every block of a layout
    has the same ``rows`` and ``order``.  Group b keeps the first
    ``W_b`` lanes of its rows (its class, or the block's width where
    that is narrower), which hold every stored entry of them.  When
    every row falls in one class whose width is the slab's, the one
    group *is* the slab, with the identity order.
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


@jax.jit
def row_lengths(values: jax.Array) -> jax.Array:
    """int32[N]: one past the last stored (nonzero) entry of each row of
    a ``[N, W]`` slab, 0 for a row with none; computed where the slab
    sits."""
    stored = values != 0.0
    last = values.shape[1] - jnp.argmax(stored[:, ::-1], axis=1)
    return jnp.where(stored.any(axis=1), last, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("widths",))
def _take_groups(idx, val, rows, widths):
    """Group b: the first ``widths[b]`` lanes of the rows ``rows[b]``,
    of ``idx`` and of ``val`` (one program a block)."""
    return (tuple(idx[r, :w] for r, w in zip(rows, widths)),
            tuple(val[r, :w] for r, w in zip(rows, widths)))


def _put_like(x: np.ndarray, like: jax.Array) -> jax.Array:
    """``x`` on the device ``like`` is committed to (default device if
    it is not committed)."""
    if getattr(like, "committed", False):
        return jax.device_put(x, like.sharding)
    return jnp.asarray(x)


def block_groups(
    slabs: Sequence[tuple[jax.Array, jax.Array]],
) -> tuple[RowGroups, ...]:
    """Every block's ``[N, W_l]`` rows grouped by length class
    (:class:`RowGroups`), one row order for all blocks.

    ``slabs`` are the layout's own ``(indices, values)`` per block, on
    any device.  Only the per-row lengths come to the host, where the
    classes and the row order are worked out; each block's groups are
    gathered from its slab on the device that holds it.
    """
    lengths = np.max(
        np.stack([np.asarray(row_lengths(v)) for _, v in slabs]), axis=0
    )
    widest = max(int(v.shape[1]) for _, v in slabs)
    n = lengths.shape[0]
    lanes = np.maximum(-(-lengths // GROUP_LANES), 1) * GROUP_LANES
    cls = np.minimum(lanes, widest)
    widths = np.unique(cls)
    if widths.size <= 1:
        w_b = int(widths[0]) if widths.size else widest
        out = []
        for idx, val in slabs:
            w = min(w_b, int(val.shape[1]))
            if w < val.shape[1]:
                idx, val = jnp.asarray(idx)[:, :w], jnp.asarray(val)[:, :w]
            ident = _put_like(np.arange(n, dtype=np.int32), val)
            out.append(RowGroups((idx,), (val,), (ident,), ident))
        return tuple(out)
    perm = np.argsort(cls, kind="stable").astype(np.int32)
    order = np.empty(n, np.int32)
    order[perm] = np.arange(n, dtype=np.int32)
    starts = np.searchsorted(cls[perm], widths)
    ends = np.append(starts[1:], n)
    out = []
    for idx, val in slabs:
        rows = tuple(_put_like(perm[a:b], val) for a, b in zip(starts, ends))
        cut = tuple(min(int(w_b), int(val.shape[1])) for w_b in widths)
        g_idx, g_val = _take_groups(idx, val, rows, cut)
        out.append(RowGroups(g_idx, g_val, rows, _put_like(order, val)))
    return tuple(out)


def block_margins(idx, val, w_block, use_kernels: bool):
    """Per-block partial margins over block-LOCAL rows (gather, no mask)."""
    if use_kernels:
        return ops.sparse_margins(idx, val, w_block)
    return local_margins(idx, val, w_block)


def group_margins(groups: RowGroups, w_block, use_kernels: bool):
    """One block's partial margins over its row groups, in row order."""
    parts = [
        block_margins(idx, val, w_block, use_kernels)
        for idx, val in zip(groups.indices, groups.values)
    ]
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts)[groups.order]


def group_scatter(groups: RowGroups, coeffs, block_dim: int):
    """sum_i coeffs_i * x^(l)_i over one block's row groups, ``coeffs``
    in row order; one group is exactly :func:`local_scatter`."""
    if len(groups.indices) == 1:
        return local_scatter(groups.indices[0], groups.values[0], coeffs,
                             block_dim)
    z = jnp.zeros((block_dim,), dtype=groups.values[0].dtype)
    for idx, val, rows in zip(groups.indices, groups.values, groups.rows):
        z = z.at[idx.reshape(-1)].add((val * coeffs[rows][:, None]).reshape(-1))
    return z


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """A :class:`BlockCSR` placed on a mesh: every array's rows split
    over the mesh's feature axes, block l's on the device that holds
    shard l, and what every block shares replicated."""

    indices: jax.Array  # int32[q*N, B]: block l's slab at rows [l*N, (l+1)*N)
    values: jax.Array  # float[q*N, B]
    groups: RowGroups  # indices/values [q*N_b, W_b] split; rows, order replicated
    labels: jax.Array  # float[N], replicated


def _row_stack(blocks: Sequence[jax.Array], sharding: NamedSharding,
               width: int | None = None) -> jax.Array:
    """``[q*R, W]`` from q ``[R, W_l]`` blocks, its rows split over the
    devices of ``sharding``: block l (its width padded with zeros to
    ``width``, default the widest) becomes the shard of the device that
    holds rows ``[l*R, (l+1)*R)``.  A block already on that device at
    that width is that shard as it is, so nothing moves or is copied."""
    width = width or max(int(b.shape[1]) for b in blocks)
    rows = int(blocks[0].shape[0])
    shape = (len(blocks) * rows, width)
    shards = []
    for device, index in sharding.addressable_devices_indices_map(shape).items():
        b = blocks[(index[0].start or 0) // rows]
        if b.shape[1] < width:
            b = jnp.pad(b, ((0, 0), (0, width - int(b.shape[1]))))
        shards.append(jax.device_put(b, device))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def _replicate(copies: Sequence[jax.Array], sharding: NamedSharding) -> jax.Array:
    """One array, replicated over ``sharding``'s devices, from copies
    that may already sit on some of them (each used where it is)."""
    shape = copies[0].shape
    where = {}
    for c in copies:
        if getattr(c, "committed", False):
            for s in c.addressable_shards:
                if s.data.shape == shape:
                    where.setdefault(s.device, s.data)
    shards = [
        where[d] if d in where else jax.device_put(copies[0], d)
        for d in sharding.addressable_devices_indices_map(shape)
    ]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


@dataclasses.dataclass(frozen=True)
class BlockCSR:
    """A PaddedCSR re-indexed into q block-local shards."""

    partition: FeaturePartition
    indices: tuple[jax.Array, ...]  # per block: int32[N, nnz_l], local ids
    values: tuple[jax.Array, ...]  # per block: float[N, nnz_l]
    labels: jax.Array  # float[N], in {-1, +1}
    # Global d.  The partition may cover up to q - 1 more feature
    # columns, which no row stores, so that a mesh gets blocks of one
    # size (FDSVRG's sharded driver pads d so).
    dim: int
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
    # Stored entries (nonzero values) over all blocks, counted when the
    # layout is built; direct constructions that leave it None count it
    # once here.
    stored: int | None = None
    # Per block: its rows grouped by length class (block_groups), which
    # the full gradient reads.  Built with the layout; direct
    # constructions that leave it None build it once here.
    groups: tuple[RowGroups, ...] | None = None
    # on_mesh's placements, one per (mesh, feature axes).
    _on_mesh: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        if self.stored is None:
            object.__setattr__(self, "stored", sum(
                int(jnp.count_nonzero(v)) for v in self.values))
        if self.groups is None:
            object.__setattr__(self, "groups", block_groups(
                list(zip(self.indices, self.values))))

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

        ``partition`` covers ``data.dim`` or, for a mesh that needs
        blocks of one size, up to q - 1 more columns, which no row
        stores.
        """
        _check_cover(partition, data.dim)
        if partition.num_blocks == 1:
            idx, val = np.asarray(data.indices), np.asarray(data.values)
            nnz_col = _count_cols(idx, val, partition.dim)
            return cls(
                partition=partition,
                indices=(data.indices,),
                values=(data.values,),
                labels=data.labels,
                dim=data.dim,
                nnz_col=(jnp.asarray(nnz_col),),
                nnz_max=data.nnz_max,
                stored=int(nnz_col.sum()),
                groups=block_groups([(data.indices, data.values)]),
            )
        idx = np.asarray(data.indices)
        val = np.asarray(data.values)
        n = idx.shape[0]
        block_indices: list[jax.Array] = []
        block_values: list[jax.Array] = []
        block_nnz_col: list[jax.Array] = []
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
            groups=block_groups(list(zip(block_indices, block_values))),
        )

    @classmethod
    def from_blocks(
        cls,
        indices: Sequence[jax.Array],
        values: Sequence[jax.Array],
        partition: FeaturePartition,
        labels: jax.Array,
        dim: int,
    ) -> "BlockCSR":
        """The layout of blocks that are already made, each on its own
        device: ``indices[l]`` / ``values[l]`` are block l's ``[N, W_l]``
        local ids and values, padded with (0, 0.0), wherever they sit.

        Nothing of a block leaves its device: its row groups are
        gathered there, and ``stored`` is counted there.  Only the
        per-row lengths (N ints a block) and the counts come to the
        host.  ``partition`` covers ``dim`` or up to q - 1 more columns,
        as in :meth:`from_padded`.  ``nnz_max`` is the widest row's
        stored count over all blocks; ``nnz_col`` is left to
        :meth:`nnz_col_block` (a host pass, which no mesh path makes).
        """
        _check_cover(partition, dim)
        indices, values = tuple(indices), tuple(values)
        if not len(indices) == len(values) == partition.num_blocks:
            raise ValueError(
                f"{len(indices)} index and {len(values)} value blocks for "
                f"a partition of {partition.num_blocks}"
            )
        n = int(values[0].shape[0])
        for l, (i, v) in enumerate(zip(indices, values)):
            if i.shape != v.shape or i.shape[0] != n:
                raise ValueError(
                    f"block {l}: indices {i.shape} and values {v.shape}, "
                    f"expected [{n}, W] for both"
                )
        with obs.span("ingest.blocks", blocks=len(values)):
            per_row = [jnp.count_nonzero(v, axis=1) for v in values]
            counts = [int(jnp.sum(c)) for c in per_row]
            widest = int(np.max(sum(np.asarray(c, np.int64) for c in per_row)))
            return cls(
                partition=partition,
                indices=indices,
                values=values,
                labels=labels,
                dim=int(dim),
                nnz_max=widest,
                stored=sum(counts),
                groups=block_groups(list(zip(indices, values))),
            )

    def stacked(
        self,
        budget: int | None = None,
        sharding: NamedSharding | None = None,
    ) -> tuple[jax.Array, jax.Array]:
        """Uniform-budget ``[q*N, B]`` index/value row stacks for
        ``shard_map``: block l's rows at ``[l*N, (l+1)*N)``.

        shard_map shards need identical shapes per worker, so every block
        is padded up to a common nnz budget (default: the max per-block
        budget).  Split the rows over the feature mesh axes and each
        worker receives only its own block's O(nnz_max/q)-wide local
        rows.  With ``sharding`` (rows split over q devices) the stacks
        are assembled from the blocks where they sit: a block already on
        its device at the common width is its shard, with no copy
        (blocks on different devices need it).
        """
        common = max(self.nnz_budgets)
        if budget is not None:
            if budget < common:
                raise ValueError(f"budget {budget} < required {common}")
            common = budget
        if sharding is not None:
            return (_row_stack(self.indices, sharding, common),
                    _row_stack(self.values, sharding, common))
        if len({d for v in self.values if getattr(v, "committed", False)
                for d in v.devices()}) > 1:
            raise ValueError(
                "the blocks sit on different devices; pass the sharding "
                "that splits the stacks' rows over them"
            )
        idx = jnp.concatenate(
            [
                jnp.pad(i, ((0, 0), (0, common - i.shape[1])))
                for i in self.indices
            ]
        )
        val = jnp.concatenate(
            [
                jnp.pad(v, ((0, 0), (0, common - v.shape[1])))
                for v in self.values
            ]
        )
        return idx, val

    def on_mesh(self, mesh, axes: Sequence[str]) -> MeshLayout:
        """The layout placed on ``mesh``, its rows split over ``axes``
        (one block a device, in the mesh's order over them): the
        :meth:`stacked` slabs, the row groups (each group's widths
        padded to the widest block's where they differ) and the labels
        replicated.  Built once per (mesh, axes) and kept: a block that
        already sits on its device at the common width is used as it is,
        with no copy; others are moved there once."""
        key = (mesh, tuple(axes))
        hit = self._on_mesh.get(key)
        if hit is not None:
            return hit
        q = self.num_blocks
        rows = NamedSharding(mesh, P(tuple(axes), None))
        repl = NamedSharding(mesh, P())
        if rows.num_devices != q:
            raise ValueError(
                f"the layout has {q} blocks; axes {tuple(axes)} of the mesh "
                f"span {rows.num_devices} devices"
            )
        g = self.groups
        indices, values = self.stacked(sharding=rows)
        placed = MeshLayout(
            indices=indices,
            values=values,
            groups=RowGroups(
                indices=tuple(_row_stack([b.indices[k] for b in g], rows)
                              for k in range(len(g[0].indices))),
                values=tuple(_row_stack([b.values[k] for b in g], rows)
                             for k in range(len(g[0].values))),
                rows=tuple(_replicate([b.rows[k] for b in g], repl)
                           for k in range(len(g[0].rows))),
                order=_replicate([b.order for b in g], repl),
            ),
            labels=_replicate([jnp.asarray(self.labels)], repl),
        )
        self._on_mesh[key] = placed
        return placed

    def nnz_total(self) -> int:
        return self.stored


def _check_cover(partition: FeaturePartition, dim: int) -> None:
    q = partition.num_blocks
    if not dim <= partition.dim < dim + q:
        raise ValueError(
            f"partition covers dim={partition.dim}, data has dim={dim} "
            f"(a partition of {q} blocks may add at most {q - 1} columns)"
        )


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


def block_prox_update(w_blk, idx, val, coef, z_blk, eta_m, reg,
                      kernel_lams: tuple[float, float, float] | None = None):
    """One block's variance-reduced proximal step (Alg 1 line 11):
    ``prox_{eta_m g}(w - eta_m * ((sum_i coef_i x^(l)_i + z) + smooth_grad
    g))`` over the sampled block-LOCAL rows ``(idx, val)``.

    Every FD driver's inner step ends here, after its own reduction of
    the margins into ``coef``.  ``reg`` is a
    :class:`repro.core.losses.Regularizer`; the prox is elementwise, so
    the step is purely block-local.  ``eta_m = 0`` (Option II's masked
    tail) leaves the block as it is.  Given ``kernel_lams``, the static
    ``(smooth_lam, prox_l1, prox_l2)`` the fused Pallas kernel bakes in,
    the step runs as :func:`repro.kernels.ops.fused_block_prox_update`,
    bit-identical in interpret mode.
    """
    if kernel_lams is not None:
        lam, lam1, lam2 = kernel_lams
        # The fused kernel scatters inside its update.
        with jax.named_scope("inner/update"):
            return ops.fused_block_prox_update(
                w_blk, idx, val, coef, z_blk, eta_m,
                lam=lam, lam1=lam1, lam2=lam2,
            )
    with jax.named_scope("inner/scatter"):
        g = local_scatter(idx, val, coef, w_blk.shape[0])
    with jax.named_scope("inner/update"):
        g = g + z_blk + reg.smooth_grad(w_blk)
        return reg.prox(w_blk - eta_m * g, eta_m)
