"""The shared, bounded BlockCSR cache service, and the slot that carries
a run's final snapshot into the next warm-started call.

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

:data:`SNAPSHOT_SLOT` keeps the full gradient and margins a
:func:`repro.api.solve` call ended on (:attr:`RunResult.final`), so the
next call, warm-started from the iterate that call returned, does not
compute them again (:class:`SnapshotSlot`).
"""

from __future__ import annotations

import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.driver import RunResult, Snapshot
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


@jax.jit
def _bits_equal(a, b):
    uint = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
    return jnp.array_equal(jax.lax.bitcast_convert_type(a, uint),
                           jax.lax.bitcast_convert_type(b, uint))


def same_bits(a, b: jax.Array) -> bool:
    """Whether ``a`` is the array ``b`` or holds its bits: the same
    shape and dtype, then one compare on ``b``'s devices."""
    if a is b:
        return True
    if not hasattr(a, "dtype") or np.shape(a) != b.shape \
            or np.dtype(a.dtype) != b.dtype:
        return False
    if isinstance(a, jax.Array) and a.sharding.device_set != b.sharding.device_set:
        a = jax.device_put(a, b.sharding)
    return bool(_bits_equal(a, b))


class SnapshotSlot:
    """The last result's final snapshot, for a call that continues from it.

    One entry: the data object (held weakly), a key of what decides the
    snapshot's computation beside the data (method, loss, layout), the
    ``w`` the result returned, and its :class:`Snapshot`.  :meth:`take`
    hands the snapshot to a call on the same data and key whose
    ``init_w`` is that returned array or holds its bits, and empties the
    slot whatever it finds; :meth:`put` keeps a new result's.  The
    per-sweep scope is :class:`BlockCache`'s: a call on other data
    (:meth:`scope`), or the data object's collection, empties the slot,
    so it never keeps an old data set's arrays alive.
    """

    def __init__(self) -> None:
        self._entry: tuple | None = None

    def scope(self, data) -> None:
        """Empty the slot unless it was filled on ``data``."""
        if self._entry is not None and self._entry[0]() is not data:
            self._entry = None

    def take(self, data, key: tuple, init_w) -> Snapshot | None:
        entry, self._entry = self._entry, None
        if entry is None or init_w is None:
            return None
        ref, entry_key, returned, final = entry
        if ref() is not data or entry_key != key \
                or not same_bits(init_w, returned):
            return None
        return final

    def put(self, data, key: tuple, result: RunResult) -> RunResult:
        if result.final is not None:
            ref = weakref.ref(data, self._collected)
            self._entry = (ref, key, result.w, result.final)
        return result

    def _collected(self, ref) -> None:
        if self._entry is not None and self._entry[0] is ref:
            self._entry = None

    def __len__(self) -> int:
        return int(self._entry is not None)


#: The process-wide slot :func:`repro.api.solve` carries snapshots in.
SNAPSHOT_SLOT = SnapshotSlot()


def block_data(data: PaddedCSR, q: int) -> BlockCSR:
    """Module-level convenience over :data:`BLOCK_CACHE`."""
    return BLOCK_CACHE.get(data, q)
