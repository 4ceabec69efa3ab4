"""The shared, bounded BlockCSR cache service.

Re-indexing a data set into the block-local
:class:`~repro.data.block_csr.BlockCSR` layout is host-side numpy work
that every FD caller repeats for the same ``(data, q)`` pair: sweeps call
:func:`repro.api.solve` many times per data set, the estimator refits,
the CLI re-runs.  This cache amortizes it once for all of them (it used
to be a private dict inside ``benchmarks/common.py``, invisible to every
non-benchmark caller).

Scoping rules (unchanged from the benchmarks-era cache, now tested where
the cache lives):

* **per-sweep scope** — a new data object evicts every entry built for
  other data sets, so a sweep over data sets never pins the previous
  set's blocks alive (the original unbounded ``id()``-keyed dict did);
  the identity check also guards against ``id()`` recycling.
* **LRU bound** — at most :attr:`BlockCache.max_entries` distinct ``q``
  values are kept for the current data set.

Streamed sources (:class:`~repro.data.pipeline.DataSource`) share the
same in-process cache through :meth:`BlockCache.get_source` — identity
keyed like arrays, so a sweep holding one source object re-ingests
nothing — layered over the on-disk slab cache
(:mod:`repro.data.ingest_cache`) when the caller passes ``cache_dir``.
"""

from __future__ import annotations

from collections import OrderedDict

from repro.core.partition import FeaturePartition, balanced
from repro.data.block_csr import BlockCSR
from repro.data.sparse import PaddedCSR


class BlockCache:
    """A bounded ``(data, partition) -> BlockCSR`` cache with per-sweep
    scope."""

    def __init__(self, max_entries: int = 4) -> None:
        self.max_entries = int(max_entries)
        self._entries: OrderedDict[
            tuple[int, int], tuple[object, BlockCSR]
        ] = OrderedDict()

    def get(
        self,
        data: PaddedCSR,
        q: int,
        partition: FeaturePartition | None = None,
    ) -> BlockCSR:
        """The BlockCSR of ``data`` at ``q`` blocks, built at most once;
        ``partition`` defaults to ``balanced(data.dim, q)`` (a mesh asks
        for its padded one, :func:`repro.core.fdsvrg_shardmap.mesh_partition`)."""
        partition = partition or balanced(data.dim, q)
        hit = self._lookup(data, partition)
        if hit is not None:
            return hit
        block = BlockCSR.from_padded(data, partition)
        self._insert(data, partition, block)
        return block

    def get_source(
        self,
        source,
        q: int,
        *,
        cache_dir: str | None = None,
        chunk_rows: int = 65536,
    ) -> BlockCSR:
        """The streamed BlockCSR of a DataSource at ``q`` blocks.

        Memory layer: identity-keyed like :meth:`get` (one ingest per
        (source object, q) while the sweep holds it).  Disk layer: with
        ``cache_dir``, a miss here goes through
        :func:`repro.data.ingest_cache.get_or_build`, so even a fresh
        process warm-loads slabs instead of parsing.
        """
        from repro.data.ingest_cache import get_or_build

        partition = balanced(source.stats().dim, q)
        hit = self._lookup(source, partition)
        if hit is not None:
            return hit
        outcome = get_or_build(
            source, partition, cache_dir=cache_dir, chunk_rows=chunk_rows
        )
        self._insert(source, partition, outcome.data)
        return outcome.data

    def _lookup(self, owner, partition: FeaturePartition) -> BlockCSR | None:
        key = (id(owner), partition.bounds)
        hit = self._entries.get(key)
        if hit is not None and hit[0] is owner:
            self._entries.move_to_end(key)
            return hit[1]
        return None

    def _insert(self, owner, partition: FeaturePartition, block: BlockCSR) -> None:
        # New owner object: the sweep moved on — drop other data sets'
        # entries (and any stale entry whose id() was recycled).
        for k in [k for k, v in self._entries.items() if v[0] is not owner]:
            del self._entries[k]
        self._entries[(id(owner), partition.bounds)] = (owner, block)
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    def values(self):
        """(data, BlockCSR) pairs, LRU order (oldest first) — tests."""
        return self._entries.values()


#: The process-wide cache :func:`repro.api.solve` uses.
BLOCK_CACHE = BlockCache()


def block_data(data: PaddedCSR, q: int) -> BlockCSR:
    """Module-level convenience over :data:`BLOCK_CACHE`."""
    return BLOCK_CACHE.get(data, q)
