"""Versioned weight snapshots + the batched sparse margin hot path.

Serving splits the estimator's ``decision_function`` into its two real
halves: a *frozen, versioned* parameter snapshot that swaps atomically
under online updates (:class:`WeightSnapshot`), and a *compiled* margin
computation over padded request batches (:class:`PredictionEngine`).

The numerics contract is the repo-wide one: the engine computes

    s_i = sum_k w[idx[i, k]] * val[i, k]        (per output column)

through :func:`repro.kernels.ops.sparse_margins` (the Pallas gather
kernel, interpret-mode off-TPU) when ``use_kernels=True`` and through
the jnp reference otherwise, and both are **bit-identical** to
``FDSVRGClassifier.decision_function`` evaluated on the same padded
rows (pinned in ``tests/test_serve_engine.py``).  Multi-output ``w ∈
R^{d×k}`` runs one kernel pass per column — exactly the per-column loop
``decision_function`` does for one-vs-rest models, so ``k > 1`` stays
bitwise too.

Two padding facts the batcher design leans on (both verified by test):

* padding extra **rows** (zero indices/values) never changes the
  surviving rows' bits — each row's reduction is independent;
* padding extra nnz **lanes** appends exact-zero addends, which XLA may
  still *reassociate* at large widths — so the bit contract with a
  reference computed at a different padded width holds only for the
  narrow widths typical of text/CTR rows (empirically ≲ 64 lanes on
  CPU); at matched width it holds always.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.data.sparse import margins_rows
from repro.kernels import ops

# The jnp reference, jit'd once.  Jit is load-bearing for the bit
# contract: XLA contracts gather·multiply·reduce the same way it does
# inside the training epochs, so this path is bit-identical to the
# Pallas kernel (pinned in tests/test_fused_kernels.py: kernel ==
# jax.jit(ref)) — the un-jitted eager call is NOT (it skips the fused
# multiply-add).  `FDSVRGClassifier.decision_function` routes through
# :func:`batched_margins` below for exactly this reason.
_ref_margins = jax.jit(margins_rows)


def batched_margins(indices, values, w, *, use_kernels: bool = False) -> np.ndarray:
    """THE serving margin computation — one definition shared by the
    engine and ``FDSVRGClassifier.decision_function``.

    ``w`` is ``[d]`` (returns ``[n]``) or ``[d, k]`` (returns ``[n, k]``,
    one kernel pass per column — bitwise equal to k binary scorings).
    ``use_kernels=True`` runs the Pallas gather kernel (interpret-mode
    off-TPU); both paths are bit-identical to each other.
    """
    with obs.span("serve.engine"):
        return _margins(indices, values, w, use_kernels)


def _margins(indices, values, w, use_kernels: bool) -> np.ndarray:
    """:func:`batched_margins` inside its ``serve.engine`` span: the
    uploads, the jitted call(s) and the download each under a span of
    their own."""
    with obs.span("serve.h2d"):
        idx = jnp.asarray(indices, dtype=jnp.int32)
        val = jnp.asarray(values)
        w = jnp.asarray(w)
    if idx.ndim != 2 or idx.shape != val.shape:
        raise ValueError(
            f"need matching [n, width] arrays, got {idx.shape} / {val.shape}"
        )
    if w.ndim not in (1, 2):
        raise ValueError(f"w must be [d] or [d, k], got shape {w.shape}")
    if idx.shape[0] == 0:
        shape = (0,) if w.ndim == 1 else (0, int(w.shape[1]))
        return np.zeros(shape, dtype=np.asarray(val).dtype)
    column = ops.sparse_margins if use_kernels else _ref_margins
    with obs.span("serve.dispatch"):
        if w.ndim == 1:
            out = [column(idx, val, w)]
        else:
            out = [column(idx, val, w[:, j]) for j in range(w.shape[1])]
    with obs.span("serve.d2h"):
        out = [np.asarray(o) for o in out]
    return out[0] if w.ndim == 1 else np.column_stack(out)


@dataclasses.dataclass(frozen=True)
class WeightSnapshot:
    """A frozen model version: ``w`` is ``[d]`` (binary) or ``[d, k]``
    (one-vs-rest multi-output), ``version`` is the monotone counter the
    engine orders publishes by."""

    w: jax.Array
    version: int

    def __post_init__(self):
        if self.w.ndim not in (1, 2):
            raise ValueError(
                f"w must be [d] or [d, k], got shape {self.w.shape}"
            )

    @property
    def dim(self) -> int:
        return int(self.w.shape[0])

    @property
    def num_outputs(self) -> int:
        return 1 if self.w.ndim == 1 else int(self.w.shape[1])

    @classmethod
    def from_dense(cls, w, version: int) -> "WeightSnapshot":
        return cls(w=jnp.asarray(w), version=version)

    @classmethod
    def from_blocks(cls, blocks, version: int) -> "WeightSnapshot":
        """Assemble from per-worker feature blocks (``[d_l]`` or
        ``[d_l, k]`` in partition order, the shape each FD worker owns
        at the end of an epoch).  Concatenation along the feature axis
        is lossless, so a block-published snapshot serves bit-identically
        to the dense one."""
        blocks = [jnp.asarray(b) for b in blocks]
        if not blocks:
            raise ValueError("from_blocks needs at least one block")
        ndims = {b.ndim for b in blocks}
        if ndims - {1, 2} or len(ndims) != 1:
            raise ValueError(
                f"blocks must all be [d_l] or all [d_l, k], got ndims {ndims}"
            )
        return cls(w=jnp.concatenate(blocks, axis=0), version=version)

    @classmethod
    def from_estimator(cls, clf, version: int) -> "WeightSnapshot":
        """From a fitted ``FDSVRGClassifier``: sklearn's ``coef_`` is
        ``[k, d]`` for one-vs-rest, the engine runs ``[d, k]``."""
        coef = np.asarray(clf.coef_)
        return cls(
            w=jnp.asarray(coef.T if coef.ndim == 2 else coef),
            version=version,
        )


class PredictionEngine:
    """Batched sparse margins against an atomically swappable snapshot.

    The engine is deliberately *dumb about requests* — it scores padded
    ``(indices, values)`` batches (the :class:`~repro.serve.batching.
    MicroBatcher`'s output) and leaves queueing, deadlines, and snapshot
    pinning to the caller.  What it owns:

    * the **current snapshot** (``publish`` swaps it; versions must be
      strictly increasing — a stale publish is a hard error, not a
      silent overwrite);
    * the **compiled-shape meter**: every distinct ``(rows, width, k,
      dtype)`` it has scored.  Each entry is one XLA compilation on both
      the kernel and jnp paths, so ``len(compiled_shapes)`` is the
      recompile count BENCH_serve gates on.
    """

    def __init__(self, snapshot: WeightSnapshot | None = None, *,
                 use_kernels: bool = False) -> None:
        self.use_kernels = use_kernels
        self._lock = threading.Lock()
        self._snapshot = snapshot
        self.compiled_shapes: set[tuple] = set()
        self.batches_served = 0
        self.rows_served = 0

    @classmethod
    def from_estimator(cls, clf, *, use_kernels: bool = False,
                       version: int = 0) -> "PredictionEngine":
        return cls(
            WeightSnapshot.from_estimator(clf, version),
            use_kernels=use_kernels,
        )

    @property
    def snapshot(self) -> WeightSnapshot:
        snap = self._snapshot
        if snap is None:
            raise ValueError("no snapshot published yet")
        return snap

    @property
    def version(self) -> int:
        return self.snapshot.version

    def publish(self, snapshot: WeightSnapshot) -> WeightSnapshot:
        """Atomically install ``snapshot``; returns the one it replaced
        (or None).  Versions are monotone: serving must never silently
        step a model backwards."""
        with self._lock:
            prev = self._snapshot
            if prev is not None:
                if snapshot.version <= prev.version:
                    raise ValueError(
                        f"publish version {snapshot.version} is not newer "
                        f"than the current {prev.version}"
                    )
                if snapshot.dim != prev.dim:
                    raise ValueError(
                        f"snapshot dim {snapshot.dim} != engine dim "
                        f"{prev.dim}"
                    )
            self._snapshot = snapshot
            return prev

    def margins(self, indices, values, *,
                snapshot: WeightSnapshot | None = None,
                batch: int | None = None) -> np.ndarray:
        """Margins for one padded batch: ``[n]`` for binary snapshots,
        ``[n, k]`` for multi-output.  ``snapshot`` overrides the current
        one (the serve loop passes the version a batch was pinned to at
        flush time — see :mod:`repro.serve.loop`); ``batch`` is the
        batch's flush sequence number (``Batch.seq``), the id of its
        ``serve.engine`` span."""
        ids = {} if batch is None else {"batch": batch}
        with obs.span("serve.engine", **ids):
            snap = self.snapshot if snapshot is None else snapshot
            values = np.asarray(values)
            n, width = values.shape if values.ndim == 2 else (0, 0)
            if n:
                self.compiled_shapes.add(
                    (n, width, snap.num_outputs, str(values.dtype),
                     self.use_kernels)
                )
            out = _margins(indices, values, snap.w, self.use_kernels)
            self.batches_served += 1
            self.rows_served += n
        return out
