"""Required work per chip of feature-distributed SVRG, counted from the
algorithm and the cell's shapes alone (``harness.work``'s rules): each of
the q chips holds d/q features and, on average, nnz/q stored ids."""

from __future__ import annotations

from harness import work


def per_chip(*, dim: int, nnz_total: int, q: int) -> tuple[int, float]:
    """``(block_dim, block_nnz)``: one chip's features and stored ids."""
    return -(-dim // q), nnz_total / q


def full_grad_bytes(*, dim: int, n: int, nnz_total: int, q: int) -> float:
    """One chip's full gradient: id and value once per stored id of its
    block (8 B), w read and z written once per block feature (4 B each),
    labels read and margins written once per row (4 B each)."""
    block_dim, block_nnz = per_chip(dim=dim, nnz_total=nnz_total, q=q)
    return (work.I32 + work.F32) * block_nnz + 2 * work.F32 * block_dim + 2 * work.F32 * n


def outer_bytes(*, dim: int, n: int, nnz_total: int, u: int, m: int, q: int) -> float:
    """One chip's outer iteration: ``work.svrg_outer_bytes`` at d/q
    features and nnz/q stored ids."""
    block_dim, block_nnz = per_chip(dim=dim, nnz_total=nnz_total, q=q)
    return work.svrg_outer_bytes(dim=block_dim, n=n, nnz_total=block_nnz, u=u, m=m)


def outer_flops(*, dim: int, n: int, nnz_total: int, u: int, m: int, q: int) -> float:
    block_dim, block_nnz = per_chip(dim=dim, nnz_total=nnz_total, q=q)
    return work.svrg_outer_flops(dim=block_dim, n=n, nnz_total=block_nnz, u=u, m=m)
