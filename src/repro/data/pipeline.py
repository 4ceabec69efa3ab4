"""Streaming sparse ingestion: ``DataSource`` -> per-worker ``BlockCSR``.

The paper's whole argument is the d >> N regime (news20 d=1.35M, webspam
d=16.6M, kdd2010 d=29.9M), where *no node ever holds the full design
matrix* — yet the original loaders materialized a global
:class:`~repro.data.sparse.PaddedCSR` on one host before any worker saw
its feature slice.  This module is the fix, three layers:

* **:class:`DataSource`** — one protocol over "where rows come from":
  an in-memory array (:class:`ArraySource`), the synthetic generator
  (:class:`SyntheticSource`), or an on-disk LibSVM file
  (:class:`LibSVMSource`).  A source yields bounded
  :class:`RowChunk`\\ s (a mini padded-CSR of ``chunk_rows`` rows), knows
  its :class:`SourceStats` up front, and has a content ``digest()`` that
  keys the on-disk slab cache (:mod:`repro.data.ingest_cache`).
* **:func:`stream_block_csr`** — incremental BlockCSR construction:
  worker l's slab is built chunk-by-chunk from only the features in
  ``[lo_l, hi_l)`` (plus the ``nnz_col`` stats the lazy-proba kernels
  need), never materializing the global ``[N, nnz_max]`` arrays.  Peak
  extra memory is one chunk plus the slabs being built
  (:func:`stream_block_slab` builds a single worker's slab for the truly
  out-of-core case).
* **the bit contract** — for every chunk size, q, and padding budget the
  streamed build is **bit-identical** to the one-shot
  ``PaddedCSR -> BlockCSR.from_padded`` path (property-tested in
  ``tests/test_ingest.py``).  The construction mirrors ``from_padded``'s
  placement exactly: entries keep file/row order, explicit zeros are
  dropped for q > 1 and kept as-is for q = 1, budgets and ``nnz_col``
  are computed over the same masks.

This module used to hold the LM token synthesizer; that moved to
:mod:`repro.data.token_stream` (a deprecation shim below keeps the old
names importable) so ``pipeline.py`` is the sparse-ingestion module its
name claims.
"""

from __future__ import annotations

import abc
import dataclasses
import hashlib
import os
from typing import Iterator

import numpy as np

from repro.data import libsvm as libsvm_lib
from repro.data.block_csr import BlockCSR, _count_cols, block_groups
from repro.data.sparse import PaddedCSR

#: Default rows-per-chunk budget; at news20-like widths (~500 stored
#: entries/row, 8 bytes each) this holds host memory near 256 MiB.
DEFAULT_CHUNK_ROWS = 65536


@dataclasses.dataclass(frozen=True)
class SourceStats:
    """What a source knows about itself before any slab is built."""

    num_instances: int
    dim: int
    nnz_max: int  # global padded-row width (>= 1 for parsed text sources)
    nnz_total: int


@dataclasses.dataclass(frozen=True)
class RowChunk:
    """A bounded slice of rows in the padded layout.

    Same conventions as :class:`~repro.data.sparse.PaddedCSR`: entries
    left-aligned in source order, padded with ``(0, 0.0)``; ``labels``
    are already canonical {-1, +1} in the values' float family.
    """

    indices: np.ndarray  # int32[c, w]
    values: np.ndarray  # float[c, w]
    labels: np.ndarray  # float[c]


class DataSource(abc.ABC):
    """Where rows come from.  Implementations must be deterministic: the
    same source yields the same chunks (hence the same slabs) every pass,
    and ``digest()`` changes iff the rows would."""

    @property
    @abc.abstractmethod
    def name(self) -> str: ...

    @abc.abstractmethod
    def stats(self) -> SourceStats: ...

    @abc.abstractmethod
    def digest(self) -> str:
        """Content digest keying the on-disk slab cache."""

    @abc.abstractmethod
    def chunks(
        self, chunk_rows: int = DEFAULT_CHUNK_ROWS
    ) -> Iterator[RowChunk]: ...

    def materialize(self) -> PaddedCSR:
        """The global padded layout (instance-sharded baselines need it).

        This IS the allocation streaming exists to avoid — callers on the
        d >> N sets should prefer :func:`stream_block_csr`.
        """
        import jax.numpy as jnp

        stats = self.stats()
        width = stats.nnz_max
        idx_parts, val_parts, lab_parts = [], [], []
        for chunk in self.chunks():
            pad = width - chunk.indices.shape[1]
            idx_parts.append(np.pad(chunk.indices, ((0, 0), (0, pad))))
            val_parts.append(np.pad(chunk.values, ((0, 0), (0, pad))))
            lab_parts.append(chunk.labels)
        return PaddedCSR(
            indices=jnp.asarray(np.vstack(idx_parts)),
            values=jnp.asarray(np.vstack(val_parts)),
            labels=jnp.asarray(np.concatenate(lab_parts)),
            dim=stats.dim,
        )


def is_source(obj) -> bool:
    return isinstance(obj, DataSource)


def as_source(obj) -> DataSource:
    """Coerce a PaddedCSR, a ``*.libsvm`` path, or a DataSource."""
    if isinstance(obj, DataSource):
        return obj
    if isinstance(obj, PaddedCSR):
        return ArraySource(obj)
    if isinstance(obj, (str, os.PathLike)):
        return LibSVMSource(os.fspath(obj))
    raise TypeError(
        f"cannot build a DataSource from {type(obj).__name__}; pass a "
        "PaddedCSR, a LibSVM file path, or a DataSource"
    )


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------


class ArraySource(DataSource):
    """An in-memory :class:`PaddedCSR`, chunked by row slices.

    Chunk width is the array's full padded width, so the q = 1 streamed
    build reproduces the arrays as-is — including the stored-explicit-zero
    / padding ambiguity ``BlockCSR.from_padded`` documents.
    """

    def __init__(self, data: PaddedCSR, *, name: str = "array") -> None:
        self._data = data
        self._name = name
        self._digest: str | None = None

    @property
    def name(self) -> str:
        return self._name

    def stats(self) -> SourceStats:
        values = np.asarray(self._data.values)
        # Exact array width, unclamped: bit-parity with from_padded
        # extends to the metadata (nnz_max) even for width-0 arrays.
        return SourceStats(
            num_instances=self._data.num_instances,
            dim=self._data.dim,
            nnz_max=self._data.nnz_max,
            nnz_total=int(np.count_nonzero(values)),
        )

    def digest(self) -> str:
        if self._digest is None:
            h = hashlib.sha256()
            h.update(f"array:v1:dim={self._data.dim}:".encode())
            for arr in (self._data.indices, self._data.values, self._data.labels):
                a = np.ascontiguousarray(np.asarray(arr))
                h.update(str((a.dtype, a.shape)).encode())
                h.update(a.tobytes())
            self._digest = h.hexdigest()
        return self._digest

    def chunks(
        self, chunk_rows: int = DEFAULT_CHUNK_ROWS
    ) -> Iterator[RowChunk]:
        if chunk_rows < 1:
            raise ValueError(f"chunk_rows >= 1 required, got {chunk_rows}")
        indices = np.asarray(self._data.indices)
        values = np.asarray(self._data.values)
        labels = np.asarray(self._data.labels)
        for lo in range(0, indices.shape[0], chunk_rows):
            hi = lo + chunk_rows
            yield RowChunk(indices[lo:hi], values[lo:hi], labels[lo:hi])

    def materialize(self) -> PaddedCSR:
        return self._data


class SyntheticSource(DataSource):
    """The synthetic generator behind a parametric digest.

    The digest is a pure function of the generation parameters (plus the
    generator's version tag), so a cache key never requires generating
    the data; the rows themselves are generated once, on first access.
    """

    def __init__(
        self,
        *,
        dim: int,
        num_instances: int,
        nnz_per_instance: int,
        seed: int = 0,
        name: str = "synthetic",
    ) -> None:
        self._dim = dim
        self._n = num_instances
        self._nnz = nnz_per_instance
        self._seed = seed
        self._name = name
        self._generated: ArraySource | None = None

    @classmethod
    def from_dataset(
        cls, dataset: str, *, scaled: bool = True, seed: int = 0
    ) -> "SyntheticSource":
        from repro.data import datasets

        spec = datasets.spec(dataset, scaled=scaled)
        return cls(
            dim=spec.dim,
            num_instances=spec.num_instances,
            nnz_per_instance=spec.nnz_per_instance,
            seed=seed,
            name=f"{dataset}{'' if scaled else '-full'}",
        )

    @property
    def name(self) -> str:
        return self._name

    def stats(self) -> SourceStats:
        # The generator emits exactly nnz_per_instance entries per row,
        # all nonzero (gamma draws), so stats need no generation.
        return SourceStats(
            num_instances=self._n,
            dim=self._dim,
            nnz_max=self._nnz,  # generated width is exactly nnz_per_instance
            nnz_total=self._n * self._nnz,
        )

    def digest(self) -> str:
        from repro.data.synthetic import GENERATOR_VERSION

        return hashlib.sha256(
            f"synthetic:v{GENERATOR_VERSION}:dim={self._dim}:n={self._n}:"
            f"nnz={self._nnz}:seed={self._seed}".encode()
        ).hexdigest()

    def _array(self) -> ArraySource:
        if self._generated is None:
            from repro.data.synthetic import make_sparse_classification

            self._generated = ArraySource(
                make_sparse_classification(
                    dim=self._dim,
                    num_instances=self._n,
                    nnz_per_instance=self._nnz,
                    seed=self._seed,
                ),
                name=self._name,
            )
        return self._generated

    def chunks(
        self, chunk_rows: int = DEFAULT_CHUNK_ROWS
    ) -> Iterator[RowChunk]:
        return self._array().chunks(chunk_rows)

    def materialize(self) -> PaddedCSR:
        return self._array().materialize()


class LibSVMSource(DataSource):
    """An on-disk LibSVM file, parsed in bounded chunks.

    The stats pass (:func:`repro.data.libsvm.scan_libsvm`) runs once per
    source object and fixes the label convention from the file's global
    label alphabet; ``dim`` defaults to ``max stored id + 1`` and may be
    overridden with the true dimensionality (files omit all-zero
    columns).  ``digest()`` is the file content's sha256 — hashing, not
    parsing, so a warm cache hit never tokenizes a line — memoized
    against ``(size, mtime_ns)``.
    """

    def __init__(self, path: str, *, dim: int | None = None) -> None:
        self.path = os.fspath(path)
        self._dim_arg = dim
        self._stats: SourceStats | None = None
        self._mapper = None
        self._digest: tuple[tuple[int, int], str] | None = None

    @property
    def name(self) -> str:
        return os.path.basename(self.path)

    def _scan(self) -> SourceStats:
        if self._stats is None:
            scanned = libsvm_lib.scan_libsvm(self.path)
            if scanned.num_instances == 0:
                raise ValueError(f"{self.path}: no data rows")
            dim = max(scanned.max_index + 1, 1)
            if self._dim_arg is not None:
                if self._dim_arg <= scanned.max_index:
                    raise ValueError(
                        f"dim={self._dim_arg} but {self.path} stores feature "
                        f"id {scanned.max_index} (0-based)"
                    )
                dim = self._dim_arg
            self._mapper = libsvm_lib.canonical_label_map(scanned.label_values)
            self._stats = SourceStats(
                num_instances=scanned.num_instances,
                dim=dim,
                nnz_max=max(1, scanned.nnz_max),
                nnz_total=scanned.nnz_total,
            )
        return self._stats

    def stats(self) -> SourceStats:
        return self._scan()

    def digest(self) -> str:
        st = os.stat(self.path)
        key = (st.st_size, st.st_mtime_ns)
        if self._digest is None or self._digest[0] != key:
            h = hashlib.sha256()
            h.update(f"libsvm:v1:dim={self._dim_arg}:".encode())
            with open(self.path, "rb") as f:
                for block in iter(lambda: f.read(1 << 20), b""):
                    h.update(block)
            self._digest = (key, h.hexdigest())
        return self._digest[1]

    def chunks(
        self, chunk_rows: int = DEFAULT_CHUNK_ROWS
    ) -> Iterator[RowChunk]:
        self._scan()  # fixes the label convention before the first chunk
        for raw_labels, indices, values in libsvm_lib.iter_libsvm_chunks(
            self.path, chunk_rows
        ):
            yield RowChunk(indices, values, self._mapper(raw_labels))

    def materialize(self) -> PaddedCSR:
        stats = self._scan()
        return libsvm_lib.load_libsvm(self.path, dim=stats.dim)


# ---------------------------------------------------------------------------
# Incremental BlockCSR construction
# ---------------------------------------------------------------------------


class _RawAccumulator:
    """q = 1: keep rows as-is (``from_padded``'s single-block fast path —
    stored explicit zeros and padding survive untouched)."""

    def __init__(self, dim: int, width: int) -> None:
        self.dim = dim
        self.width = width
        self._idx: list[np.ndarray] = []
        self._val: list[np.ndarray] = []

    def add(self, idx: np.ndarray, val: np.ndarray) -> None:
        pad = self.width - idx.shape[1]
        if pad < 0:
            raise ValueError(
                f"chunk width {idx.shape[1]} exceeds the source's declared "
                f"nnz_max {self.width}"
            )
        self._idx.append(np.pad(idx, ((0, 0), (0, pad))))
        self._val.append(np.pad(val, ((0, 0), (0, pad))))

    def finalize(self, lane_multiple: int):
        del lane_multiple  # from_padded's q=1 path keeps budgets as-is
        idx = np.vstack(self._idx) if self._idx else np.zeros((0, self.width), np.int32)
        val = np.vstack(self._val) if self._val else np.zeros((0, self.width), np.float32)
        return idx, val, _count_cols(idx, val, self.dim)


class _BlockAccumulator:
    """One feature block's compacted entries, chunk by chunk.

    Mirrors ``BlockCSR.from_padded``'s per-block pass exactly — the mask,
    the row-major compaction order, the budget rule, the ``nnz_col``
    counts — restricted to one chunk of rows at a time.  ``finalize``
    pastes the per-chunk compacted strips into the ``[N, budget]`` slab.
    """

    def __init__(self, lo: int, hi: int) -> None:
        self.lo = lo
        self.hi = hi
        self._strips: list[tuple[np.ndarray, np.ndarray]] = []
        self._rows = 0
        self._max_count = 0
        self._nnz_col = np.zeros(hi - lo, dtype=np.int64)

    def add(self, idx: np.ndarray, val: np.ndarray) -> None:
        in_blk = (idx >= self.lo) & (idx < self.hi) & (val != 0.0)
        counts = in_blk.sum(axis=1)
        c = idx.shape[0]
        w = int(counts.max()) if c else 0
        self._max_count = max(self._max_count, w)
        out_idx = np.zeros((c, w), dtype=np.int32)
        out_val = np.zeros((c, w), dtype=val.dtype)
        rows, cols = np.nonzero(in_blk)  # row-major: preserves row order
        pos = np.arange(rows.size) - np.searchsorted(rows, rows, side="left")
        out_idx[rows, pos] = idx[rows, cols] - self.lo
        out_val[rows, pos] = val[rows, cols]
        self._strips.append((out_idx, out_val))
        self._rows += c
        if rows.size:
            self._nnz_col += np.bincount(
                out_idx[rows, pos].astype(np.int64), minlength=self.hi - self.lo
            )

    def finalize(self, lane_multiple: int):
        budget = max(1, self._max_count)
        budget += (-budget) % lane_multiple
        dtype = self._strips[0][1].dtype if self._strips else np.float32
        indices = np.zeros((self._rows, budget), dtype=np.int32)
        values = np.zeros((self._rows, budget), dtype=dtype)
        row0 = 0
        for s_idx, s_val in self._strips:
            c, w = s_idx.shape
            if w:
                indices[row0 : row0 + c, :w] = s_idx
                values[row0 : row0 + c, :w] = s_val
            row0 += c
        return indices, values, self._nnz_col.astype(np.int32)


def _accumulators(partition, block_ids, width):
    out = {}
    for l in block_ids:
        if partition.num_blocks == 1:
            out[l] = _RawAccumulator(partition.dim, width)
        else:
            lo, hi = partition.block(l)
            out[l] = _BlockAccumulator(lo, hi)
    return out


def stream_block_csr(
    source: DataSource,
    partition,
    *,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    lane_multiple: int = 1,
) -> BlockCSR:
    """Build the full per-worker :class:`BlockCSR` by streaming ``source``.

    Bit-identical to ``BlockCSR.from_padded(source.materialize(),
    partition, lane_multiple=...)`` for any ``chunk_rows`` — that is the
    ingestion contract (property-tested) — without ever allocating the
    global ``[N, nnz_max]`` padded arrays.  Peak host memory is one chunk
    plus the compacted slabs themselves.
    """
    stats = source.stats()
    if partition.dim != stats.dim:
        raise ValueError(
            f"partition covers dim={partition.dim}, source has "
            f"dim={stats.dim}"
        )
    q = partition.num_blocks
    acc = _accumulators(partition, range(q), stats.nnz_max)
    labels_parts: list[np.ndarray] = []
    for chunk in source.chunks(chunk_rows):
        labels_parts.append(chunk.labels)
        for a in acc.values():
            a.add(chunk.indices, chunk.values)
    return _assemble(
        partition, acc, labels_parts, stats, lane_multiple, source
    )


def stream_block_slab(
    source: DataSource,
    partition,
    block_id: int,
    *,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    lane_multiple: int = 1,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """ONE worker's ``(indices, values, nnz_col)`` slab — the truly
    out-of-core shape: worker ``block_id`` parses the stream and keeps
    only its own ``[lo, hi)`` entries (O(nnz_l) memory, q parse passes
    for q workers instead of one — the :mod:`repro.data.ingest_cache`
    amortizes that to once ever)."""
    stats = source.stats()
    if partition.dim != stats.dim:
        raise ValueError(
            f"partition covers dim={partition.dim}, source has "
            f"dim={stats.dim}"
        )
    acc = _accumulators(partition, [block_id], stats.nnz_max)[block_id]
    for chunk in source.chunks(chunk_rows):
        acc.add(chunk.indices, chunk.values)
    return acc.finalize(lane_multiple)


def _assemble(partition, acc, labels_parts, stats, lane_multiple, source):
    import jax.numpy as jnp

    q = partition.num_blocks
    block_indices, block_values, block_nnz_col = [], [], []
    stored = 0
    for l in range(q):
        idx, val, nnz_col = acc[l].finalize(lane_multiple)
        block_indices.append(jnp.asarray(idx))
        block_values.append(jnp.asarray(val))
        block_nnz_col.append(jnp.asarray(nnz_col))
        stored += int(nnz_col.sum())
    labels = (
        np.concatenate(labels_parts)
        if labels_parts
        else np.zeros((0,), np.float32)
    )
    if labels.shape[0] != stats.num_instances:
        raise ValueError(
            f"source {source.name!r} declared {stats.num_instances} "
            f"instances but yielded {labels.shape[0]} rows"
        )
    return BlockCSR(
        partition=partition,
        indices=tuple(block_indices),
        values=tuple(block_values),
        labels=jnp.asarray(labels),
        dim=stats.dim,
        nnz_col=tuple(block_nnz_col),
        nnz_max=stats.nnz_max,
        stored=stored,
        groups=block_groups(list(zip(block_indices, block_values))),
    )


# ---------------------------------------------------------------------------
# Streaming inference helpers (serving without materializing)
# ---------------------------------------------------------------------------


def streamed_margins(
    source: DataSource,
    w,
    *,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
) -> np.ndarray:
    """``w^T x_i`` for every row of ``source``, one chunk at a time.

    ``w`` is ``[d]`` (returns ``[n]``) or multi-output ``[d, k]``
    (returns ``[n, k]`` in ONE pass over the source — column ``j`` is
    computed exactly like the ``k = 1`` call with ``w[:, j]``, so a
    one-vs-rest model never pays k parse passes over a file)."""
    w = np.asarray(w)
    if w.ndim not in (1, 2):
        raise ValueError(f"w must be [d] or [d, k], got shape {w.shape}")
    parts = []
    for chunk in source.chunks(chunk_rows):
        if w.ndim == 2:
            # One gather per column, NOT w[chunk.indices][:, :, j]: einsum
            # over a strided column slice reduces in a different order
            # than over the contiguous gather the k = 1 path sees, and
            # the per-column bit contract would quietly break.
            parts.append(
                np.stack(
                    [
                        np.einsum(
                            "rk,rk->r", w[:, j][chunk.indices], chunk.values
                        )
                        for j in range(w.shape[1])
                    ],
                    axis=1,
                )
            )
        else:
            parts.append(
                np.einsum("rk,rk->r", w[chunk.indices], chunk.values)
            )
    if parts:
        return np.concatenate(parts)
    shape = (0,) if w.ndim == 1 else (0, w.shape[1])
    return np.zeros(shape, dtype=w.dtype)


def source_labels(
    source: DataSource, *, chunk_rows: int = DEFAULT_CHUNK_ROWS
) -> np.ndarray:
    """The canonical {-1, +1} labels, streamed."""
    parts = [chunk.labels for chunk in source.chunks(chunk_rows)]
    return (
        np.concatenate(parts) if parts else np.zeros((0,), dtype=np.float32)
    )


# ---------------------------------------------------------------------------
# Deprecation shim: the LM token pipeline moved to repro.data.token_stream
# ---------------------------------------------------------------------------

_TOKEN_STREAM_NAMES = ("PipelineConfig", "batches", "_token_stream")


def __getattr__(name: str):
    if name in _TOKEN_STREAM_NAMES:
        import warnings

        warnings.warn(
            f"repro.data.pipeline.{name} moved to repro.data.token_stream; "
            "this alias will be removed",
            DeprecationWarning,
            stacklevel=2,
        )
        from repro.data import token_stream

        return getattr(token_stream, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
