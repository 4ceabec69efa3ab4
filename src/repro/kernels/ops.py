"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile multiples, layout munging ([d] vectors to the 2-D
layouts the TPU tiles want), backend selection (interpret=True off-TPU so
the same code validates on CPU), and exposes shapes the rest of the
framework uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fd_matvec import fd_matvec
from repro.kernels.flash_decode import flash_decode
from repro.kernels.fused_update import fused_update
from repro.kernels.lazy_update import (
    lazy_catchup,
    lazy_flush,
    lazy_proba_update,
    lazy_touch_update,
    step_corrections,
)
from repro.kernels.logistic_grad import logistic_grad
from repro.kernels.prox_update import prox_update
from repro.kernels.sparse_margin import LANES, sparse_margin
from repro.kernels.svrg_update import svrg_update


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# SMEM (1 MiB on a v5e) holds a double-buffered window of a row tile's
# ids: 32768 int32 entries per buffer leaves most of it free.
_SMEM_ID_WINDOW = 1 << 15
# Rows of a lane-major vector per grid step of the update kernel: 1 MiB
# of f32 per block, so w, z and the output chunk, double-buffered, stay
# well inside the default scoped VMEM.
_UPDATE_CHUNK_ROWS = 2048


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_major(v: jax.Array, rows: int) -> jax.Array:
    """[d] -> [rows, 128], zero-padded: feature j at (j // 128, j % 128)."""
    return jnp.pad(v, (0, rows * LANES - v.shape[0])).reshape(rows, LANES)


def margin_row_tile(n: int, width: int) -> int:
    """Row tile of :func:`sparse_margins`: as many rows as the SMEM id
    window holds at this width (a multiple of 8), never more than the
    call has."""
    cap = max(8, (_SMEM_ID_WINDOW // max(width, 1)) // 8 * 8)
    return min(_round_up(max(n, 1), 8), cap)


def sparse_margins(
    indices: jax.Array,  # int32[N, nnz_l], block-LOCAL ids (BlockCSR rows)
    values: jax.Array,  # [N, nnz_l]
    w_block: jax.Array,  # [d_block]
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    scalar_loops: bool | None = None,
) -> jax.Array:  # [N]
    """Fused gather-margin over one block's local CSR rows (the FD-SVRG
    margin hot path).  On the TPU the gather runs as the kernel's scalar
    loop, the nnz axis is padded to the lane width with (id 0, value 0)
    entries, and ``block_rows=None`` derives the row tile from the shapes
    (:func:`margin_row_tile`).  The interpreter runs the jnp-gather body
    at the caller's width with all rows in one tile — the shape the
    bit-identity contract is stated for, since XLA:CPU picks a row
    reduction's order by shape; ``scalar_loops=True`` runs the TPU body
    under it instead."""
    interpret = _interpret_default() if interpret is None else interpret
    scalar_loops = not interpret if scalar_loops is None else scalar_loops
    n = indices.shape[0]
    if not interpret:
        indices = _pad_to(indices, 1, LANES)
        values = _pad_to(values, 1, LANES)
    if block_rows is None:
        width = indices.shape[1]
        block_rows = margin_row_tile(n, width) if scalar_loops else max(n, 1)
    idx2 = _pad_to(indices, 0, block_rows)
    val2 = _pad_to(values, 0, block_rows)
    w_rows = _round_up(max(-(-w_block.shape[0] // LANES), 1), 8)
    out = sparse_margin(
        _lane_major(w_block, w_rows), idx2, val2, block_rows=block_rows,
        scalar_loops=scalar_loops, interpret=interpret,
    )
    return out[:n]


def fused_block_update(
    w_block: jax.Array,  # [d_block]
    indices: jax.Array,  # int32[u, nnz_l], block-LOCAL ids
    values: jax.Array,  # [u, nnz_l]
    coef: jax.Array,  # [u]
    z_block: jax.Array,  # [d_block]
    eta: jax.Array | float,  # runtime scalar (eta * option mask)
    *,
    lam: float,
    interpret: bool | None = None,
) -> jax.Array:  # [d_block]
    """Fused scatter-grad + variance-reduced parameter update on one
    block: w - eta * (scatter(coef * x) + z + lam * w) in a single pass
    (L2 family; lam = 0 covers the unregularized path)."""
    interpret = _interpret_default() if interpret is None else interpret
    d = w_block.shape[0]
    out = fused_update(
        w_block[None, :],
        indices,
        values,
        coef[None, :],
        z_block[None, :],
        jnp.asarray(eta, dtype=w_block.dtype)[None, None],
        lam=lam,
        interpret=interpret,
    )
    return out[0, :d]


def fused_block_prox_update(
    w_block: jax.Array,  # [d_block]
    indices: jax.Array,  # int32[u, nnz_l], block-LOCAL ids
    values: jax.Array,  # [u, nnz_l]
    coef: jax.Array,  # [u]
    z_block: jax.Array,  # [d_block]
    eta: jax.Array | float,  # runtime scalar (eta * option mask)
    *,
    lam: float,  # smooth L2 coefficient (the classic 'l2' path)
    lam1: float = 0.0,  # L1 strength handled by the fused prox
    lam2: float = 0.0,  # elastic-net L2 strength handled by the fused prox
    interpret: bool | None = None,
    scalar_loops: bool | None = None,
) -> jax.Array:  # [d_block]
    """Fused scatter-grad + proximal variance-reduced update on one block:
    prox_{eta*g}(w - eta * (scatter(coef * x) + z + lam * w)) in a single
    pass.  Covers the whole regularizer family — lam1 = lam2 = 0 elides
    the prox stages, reproducing :func:`fused_block_update` bit-exactly;
    the prox is elementwise (paper eq. 3), so it stays block-local.  On
    the TPU the scatter runs as the kernel's scalar loop over chunks of
    the block; the interpreter runs the ``.at[].add`` body in one chunk
    unless ``scalar_loops=True`` asks for the TPU body."""
    interpret = _interpret_default() if interpret is None else interpret
    scalar_loops = not interpret if scalar_loops is None else scalar_loops
    d = w_block.shape[0]
    rows = max(-(-d // LANES), 1)
    chunks = -(-rows // _UPDATE_CHUNK_ROWS) if scalar_loops else 1
    chunk_rows = _round_up(-(-rows // chunks), 8)
    rows = chunk_rows * chunks
    out = prox_update(
        _lane_major(w_block, rows),
        indices,
        values,
        coef,
        _lane_major(z_block, rows),
        jnp.asarray(eta, dtype=w_block.dtype).reshape(1),
        chunk_rows=chunk_rows,
        lam=lam,
        lam1=lam1,
        lam2=lam2,
        scalar_loops=scalar_loops,
        interpret=interpret,
    )
    return out.reshape(-1)[:d]


def _require_interpret(kernel: str, interpret: bool) -> None:
    """Three lazy kernels gather and scatter with jnp indexing, which
    Mosaic cannot lower; compiled for a TPU they fail loudly here instead
    of at an opaque lowering error (or a silent fallback).  The flush is
    elementwise over the block and does lower."""
    if not interpret:
        raise NotImplementedError(
            f"{kernel}: this Pallas kernel has no TPU lowering (its "
            "gather/scatter needs the scalar-loop rewrite the margin and "
            "prox kernels have); run lazy_updates with use_kernels=False "
            "on a TPU, or interpret=True on CPU"
        )


def _i32_scalar(x) -> jax.Array:
    return jnp.asarray(x, dtype=jnp.int32)[None, None]


def lazy_block_catchup(
    w_block: jax.Array,  # [d_block]
    last_block: jax.Array,  # int32[d_block]
    z_block: jax.Array,  # [d_block]
    indices: jax.Array,  # int32[u, nnz_l], block-LOCAL ids
    eta: jax.Array | float,  # UNMASKED step size
    m: jax.Array | int,  # current inner-step index
    stop: jax.Array | int,  # number of active (unmasked) steps this epoch
    *,
    lam: jax.Array | float,  # smooth strength — RUNTIME operand (see kernel)
    lam1: float = 0.0,
    lam2: float = 0.0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:  # ([d_block], int32[d_block])
    """Exact-lazy catch-up: replay the deferred decay of every feature
    touched at inner step ``m`` (see :mod:`repro.kernels.lazy_update`),
    returning the caught-up block and the updated ``last`` counters."""
    interpret = _interpret_default() if interpret is None else interpret
    _require_interpret("lazy_block_catchup", interpret)
    d = w_block.shape[0]
    w_out, last_out = lazy_catchup(
        w_block[None, :],
        last_block[None, :],
        z_block[None, :],
        indices,
        jnp.asarray(lam, dtype=w_block.dtype)[None, None],
        jnp.asarray(eta, dtype=w_block.dtype)[None, None],
        _i32_scalar(m),
        _i32_scalar(stop),
        lam1=lam1,
        lam2=lam2,
        interpret=interpret,
    )
    return w_out[0, :d], last_out[0, :d]


def lazy_block_touch_update(
    w_block: jax.Array,  # [d_block], caught up at the touched ids
    indices: jax.Array,  # int32[u, nnz_l], block-LOCAL ids
    values: jax.Array,  # [u, nnz_l]
    coef: jax.Array,  # [u]
    z_block: jax.Array,  # [d_block]
    eta: jax.Array | float,  # masked step size (eta * option mask)
    *,
    lam: float,
    lam1: float = 0.0,
    lam2: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:  # [d_block]
    """Exact-lazy eager half-step: the dense prox update evaluated only at
    the touched lanes — O(u * nnz_l) instead of O(d_block)."""
    interpret = _interpret_default() if interpret is None else interpret
    _require_interpret("lazy_block_touch_update", interpret)
    d = w_block.shape[0]
    out = lazy_touch_update(
        w_block[None, :],
        indices,
        values,
        coef[None, :],
        z_block[None, :],
        jnp.asarray(eta, dtype=w_block.dtype)[None, None],
        lam=lam,
        lam1=lam1,
        lam2=lam2,
        interpret=interpret,
    )
    return out[0, :d]


def lazy_block_flush(
    w_block: jax.Array,  # [d_block]
    last_block: jax.Array,  # int32[d_block]
    z_block: jax.Array,  # [d_block]
    eta: jax.Array | float,  # UNMASKED step size
    total: jax.Array | int,  # total inner steps M this epoch
    stop: jax.Array | int,  # number of active steps
    *,
    lam: jax.Array | float,  # smooth strength — RUNTIME operand (see kernel)
    lam1: float = 0.0,
    lam2: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:  # [d_block]
    """Epoch-end reconciliation: replay every feature's deferred steps so
    the block equals the dense iterate after all M inner steps."""
    interpret = _interpret_default() if interpret is None else interpret
    d = w_block.shape[0]
    out = lazy_flush(
        w_block[None, :],
        last_block[None, :],
        z_block[None, :],
        jnp.asarray(lam, dtype=w_block.dtype)[None, None],
        jnp.asarray(eta, dtype=w_block.dtype)[None, None],
        _i32_scalar(total),
        _i32_scalar(stop),
        lam1=lam1,
        lam2=lam2,
        interpret=interpret,
    )
    return out[0, :d]


def lazy_block_proba_update(
    w_block: jax.Array,  # [d_block]
    indices: jax.Array,  # int32[u, nnz_l], block-LOCAL ids
    values: jax.Array,  # [u, nnz_l]
    coef: jax.Array,  # [u]
    z_block: jax.Array,  # [d_block]
    corr_block: jax.Array,  # [d_block] step corrections (step_corrections)
    eta: jax.Array | float,  # masked step size (eta * option mask)
    *,
    lam: float,
    lam1: float = 0.0,
    lam2: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:  # [d_block]
    """Probabilistic lazy step: touched features only, decay scaled by the
    per-feature corrections so the expected update is unbiased."""
    interpret = _interpret_default() if interpret is None else interpret
    _require_interpret("lazy_block_proba_update", interpret)
    d = w_block.shape[0]
    out = lazy_proba_update(
        w_block[None, :],
        indices,
        values,
        coef[None, :],
        z_block[None, :],
        corr_block[None, :],
        jnp.asarray(eta, dtype=w_block.dtype)[None, None],
        lam=lam,
        lam1=lam1,
        lam2=lam2,
        interpret=interpret,
    )
    return out[0, :d]


def margins_dense(
    w: jax.Array,  # [d]
    data: jax.Array,  # [d, N]
    *,
    block_k: int = 512,
    block_n: int = 256,
    interpret: bool | None = None,
) -> jax.Array:  # [N]
    """S = wᵀD, the full-gradient-phase margins for one feature block."""
    interpret = _interpret_default() if interpret is None else interpret
    d, n = data.shape
    w2 = _pad_to(w[:, None], 0, block_k)
    d2 = _pad_to(_pad_to(data, 0, block_k), 1, block_n)
    out = fd_matvec(w2, d2, block_k=block_k, block_n=block_n, interpret=interpret)
    return out[0, :n]


def loss_and_grad(
    s: jax.Array,  # [N]
    y: jax.Array,  # [N]
    *,
    block: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused logistic loss values + margin derivatives."""
    interpret = _interpret_default() if interpret is None else interpret
    n = s.shape[0]
    s2 = _pad_to(s[None, :], 1, block)
    y2 = _pad_to(y[None, :], 1, block, value=1.0)
    loss, dloss = logistic_grad(s2, y2, block=block, interpret=interpret)
    return loss[0, :n], dloss[0, :n]


def svrg_dense_update(
    w: jax.Array,  # [d]
    g_sparse: jax.Array,  # [d]
    z: jax.Array,  # [d]
    *,
    eta: float,
    lam: float,
    block: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused w' = (1-eta*lam) w - eta (g_sparse + z)   (L2 path)."""
    interpret = _interpret_default() if interpret is None else interpret
    d = w.shape[0]
    w2 = _pad_to(w[None, :], 1, block)
    g2 = _pad_to(g_sparse[None, :], 1, block)
    z2 = _pad_to(z[None, :], 1, block)
    out = svrg_update(w2, g2, z2, eta=eta, lam=lam, block=block, interpret=interpret)
    return out[0, :d]


def decode_attention(
    q: jax.Array,  # [H, Dh] one token's query heads
    k: jax.Array,  # [S, Hkv, Dh] cache
    v: jax.Array,  # [S, Hkv, Dh]
    *,
    length: jax.Array | int,  # valid cache prefix
    scale: float | None = None,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:  # [H, Dh]
    """Flash-decoding over the KV cache (one token, GQA)."""
    interpret = _interpret_default() if interpret is None else interpret
    h, dh = q.shape
    s, hkv, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    scale = dh ** -0.5 if scale is None else scale

    s_pad = s + ((-s) % block_s)
    kp = _pad_to(k, 0, block_s)
    vp = _pad_to(v, 0, block_s)
    bias = jnp.where(jnp.arange(s_pad)[None, :] < length, 0.0, -1e30).astype(
        jnp.float32
    )
    qg = q.reshape(hkv, group, dh)
    out = flash_decode(
        qg, kp, vp, bias, scale=scale, block_s=block_s, interpret=interpret
    )
    return out.reshape(h, dh)


__all__ = [
    "sparse_margins",
    "fused_block_update",
    "fused_block_prox_update",
    "lazy_block_catchup",
    "lazy_block_touch_update",
    "lazy_block_flush",
    "lazy_block_proba_update",
    "step_corrections",
    "margins_dense",
    "loss_and_grad",
    "svrg_dense_update",
    "decode_attention",
    "ref",
]
