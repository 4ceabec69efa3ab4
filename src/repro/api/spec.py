"""`ExperimentSpec` — the one declarative problem description every caller
hands to :func:`repro.api.solve`.

Before this module, each of the seven optimizer drivers had its own
positional signature, and the only method-dispatching facade
(``benchmarks.common.run_method``) was a private benchmark helper that
hoarded the paper's per-method conventions and took the regularizer
*twice* (a ``lam`` float and a ``Regularizer`` whose ``lam`` had to
match).  ``ExperimentSpec`` is the fix:

* **one regularizer** — a single :class:`repro.core.losses.Regularizer`;
  the headline strength is ``spec.reg.lam``, there is no second argument
  to disagree with it;
* **"paper" auto-defaults** — ``eta``, ``batch_size``, and
  ``inner_steps`` default to the sentinel string ``"paper"``, resolved
  per method by the registry (the ``m = N/u`` rule, the per-method step
  sizes, the inner-step cap) so a spec that names only a dataset and a
  method runs at the repo's Table-1-scaled operating point;
* **loud validation** — structural errors (no data, both ``dataset`` and
  ``data``, bad option) fail here; capability mismatches (``use_kernels``
  on a driver that doesn't support it, a mesh on a non-shard_map method)
  fail inside :func:`repro.api.solve` against the registry's
  :class:`~repro.api.registry.MethodInfo` record.

The spec is frozen: a sweep can hold thousands of them, derive variants
with :func:`dataclasses.replace`, and trust that none mutated under it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.core import losses as losses_lib
from repro.data.sparse import PaddedCSR
from repro.dist import ClusterModel

#: Sentinel for "resolve this per method from the registry's paper defaults".
PAPER = "paper"


@dataclasses.dataclass(frozen=True, eq=False)
class ExperimentSpec:
    """A complete, declarative description of one optimization run.

    Exactly one of ``dataset`` (a :mod:`repro.data.datasets` key),
    ``data`` (an in-memory :class:`~repro.data.sparse.PaddedCSR`), or
    ``source`` (a :class:`~repro.data.pipeline.DataSource` or a LibSVM
    file path — the streaming out-of-core path) must be set.
    ``eq=False``: specs carry device arrays (``data``, ``init_w``), so
    identity — not elementwise comparison — is the right equality.
    """

    method: str
    dataset: str | None = None
    data: PaddedCSR | None = None
    # Streaming ingestion (repro.data.pipeline): a DataSource instance or
    # a path to a LibSVM file.  Worker slabs are built incrementally —
    # bit-identical to the in-memory path — and never materialize the
    # global matrix; methods must advertise supports_streaming.
    source: Any | None = None
    # On-disk slab cache for source= runs (repro.data.ingest_cache); None
    # disables caching.  Warm hits skip parsing entirely.
    data_cache_dir: str | None = None
    # Host-memory bound for streamed parsing, in rows per chunk.
    ingest_chunk_rows: int = 65536
    loss: str = "logistic"
    reg: losses_lib.Regularizer = losses_lib.l2(1e-4)  # paper §5.3 default
    q: int | None = None  # workers; None -> dataset default (or 1 for raw data)
    eta: float | str = PAPER
    batch_size: int | str = PAPER
    inner_steps: int | str = PAPER
    outer_iters: int = 6
    option: str = "I"  # Algorithm 2 Option I/II
    seed: int = 0
    use_kernels: bool = False
    # Lazy O(nnz) inner steps (delayed-decay replay over BlockCSR):
    # None -> the paper-faithful dense inner step; "exact" -> bitwise-
    # equivalent catch-up replay; "proba" -> unbiased probabilistic decay.
    lazy_updates: str | None = None
    cluster: ClusterModel | None = None  # None -> the backend's default
    init_w: jax.Array | None = None  # warm start (None -> zeros)
    # Outer-loop checkpoint/resume (methods with supports_checkpoint):
    # a rolling checkpoint under checkpoint_dir every checkpoint_every
    # outers; resume=True restores it when present (resume is proven
    # bit-identical to the uninterrupted run).
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    resume: bool = False
    # shard_map-only knobs (validated against MethodInfo.needs_mesh):
    mesh: Any | None = None  # jax Mesh; None -> ("model",) over all local devices
    tree_mode: str = "psum"  # "psum" | "butterfly"

    def __post_init__(self) -> None:
        given = sum(
            x is not None for x in (self.dataset, self.data, self.source)
        )
        if given != 1:
            raise ValueError(
                "exactly one of dataset= (a repro.data.datasets key), "
                "data= (a PaddedCSR), or source= (a DataSource / LibSVM "
                "path) must be set"
            )
        if self.ingest_chunk_rows < 1:
            raise ValueError(
                f"ingest_chunk_rows >= 1 required, got "
                f"{self.ingest_chunk_rows!r}"
            )
        if self.data_cache_dir is not None and self.source is None:
            raise ValueError(
                "data_cache_dir= only applies to source= runs (the "
                "in-memory paths have nothing to cache on disk)"
            )
        if self.option not in ("I", "II"):
            raise ValueError(f"option must be 'I' or 'II', got {self.option!r}")
        if not isinstance(self.reg, losses_lib.Regularizer):
            raise TypeError(
                f"reg must be a repro.core.losses.Regularizer (got "
                f"{type(self.reg).__name__}); the spec takes ONE regularizer "
                "— there is no separate lam argument to mismatch it with"
            )
        if self.loss not in losses_lib.LOSSES:
            raise ValueError(
                f"unknown loss {self.loss!r}; known: "
                f"{sorted(losses_lib.LOSSES)}"
            )
        for field, value in (
            ("eta", self.eta), ("batch_size", self.batch_size),
            ("inner_steps", self.inner_steps),
        ):
            if isinstance(value, str):
                if value != PAPER:
                    raise ValueError(
                        f"{field} must be a number or the sentinel "
                        f"{PAPER!r}, got {value!r}"
                    )
            elif field == "eta":
                if value <= 0:
                    raise ValueError(f"eta > 0 required, got {value!r}")
            elif value < 1:
                raise ValueError(f"{field} >= 1 required, got {value!r}")
        if self.outer_iters < 1:
            raise ValueError(
                f"outer_iters >= 1 required, got {self.outer_iters!r}"
            )
        if self.q is not None and self.q < 1:
            raise ValueError("q >= 1 required")
        if self.lazy_updates not in (None, "exact", "proba"):
            raise ValueError(
                f"lazy_updates must be None, 'exact', or 'proba', got "
                f"{self.lazy_updates!r}"
            )
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every >= 1 required, got {self.checkpoint_every!r}"
            )
        if self.checkpoint_dir is None and self.resume:
            raise ValueError(
                "resume=True needs checkpoint_dir= (there is nothing to "
                "resume from without one)"
            )

    def replace(self, **changes) -> "ExperimentSpec":
        """Derive a variant spec (sweeps: ``spec.replace(reg=...)``)."""
        return dataclasses.replace(self, **changes)
