"""Required work, counted from the algorithm and the cell's shapes alone.

Nothing here looks at how the program does the work: padding lanes,
padding rows, a dense step where a lazy one would do, a second pass
where one would do, none of it counts.  A change to the program that
does the same work with fewer bytes reads a higher share, never a share
above what the algorithm needs.  Bytes are float32 values and int32 ids.
"""

from __future__ import annotations

import json
from pathlib import Path

F32 = I32 = 4
PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def svrg_outer_bytes(*, dim: int, n: int, nnz_total: int, u: int, m: int) -> float:
    """Bytes one outer iteration of SVRG with L2 must move, done the way
    the exact lazy variant does it.

    * full gradient: ids and values once per stored entry, w read and z
      written once, labels read and margins written once per row;
    * each of the ``m`` inner steps: for the ``u`` sampled rows, ids and
      values plus a read and a write of w at every stored entry (the
      mean row's worth), and the row's label and anchor margin;
    * per epoch one dense pass for the deferred z + lam*w terms: read w,
      read z, write w.
    """
    row_nnz = nnz_total / n
    full_grad = (I32 + F32) * nnz_total + 2 * F32 * dim + 2 * F32 * n
    inner_step = u * row_nnz * (I32 + 3 * F32) + u * 2 * F32
    dense_pass = 3 * F32 * dim
    return full_grad + m * inner_step + dense_pass


def svrg_outer_flops(*, dim: int, n: int, nnz_total: int, u: int, m: int) -> float:
    """Multiply-adds of the same lazy outer: two per stored entry in the
    full gradient (margin and scatter), two per touched entry per inner
    step, two per coordinate for the dense pass."""
    row_nnz = nnz_total / n
    return 2 * 2 * nnz_total + m * 2 * 2 * u * row_nnz + 2 * 2 * dim


def svrg_outer_rows(*, n: int, u: int, m: int) -> int:
    """Rows one outer visits: N for the full gradient, u per inner step."""
    return n + m * u


def margin_bytes(stored_ids: int, requests: int) -> float:
    """Serving: 12 bytes per stored id (id, value, gathered w) and 4 out
    per request."""
    return (I32 + 2 * F32) * stored_ids + F32 * requests


def margin_flops(stored_ids: int) -> float:
    return 2.0 * stored_ids


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(Path(path).read_text())
    try:
        return dict(table["devices"][device_kind], source=table["source"])
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; known: "
            f"{sorted(table['devices'])}"
        ) from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Least time the chip could take over the time measured, in %, and
    which of the two bounds sets that least time."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
