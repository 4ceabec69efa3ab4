"""Roofline extraction from compiled artifacts (see ROOFLINE ANALYSIS).

Three terms, per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
    memory     = HLO_bytes / (chips * 819e9 B/s HBM)
    collective = collective_bytes / (chips * 50e9 B/s ICI link)

``cost_analysis()`` reports per-device FLOPs/bytes for the SPMD-partitioned
module, so we multiply back by ``chips`` where needed — conventions are
normalized here so the table always reads "total work / total capability".

collective_bytes comes from parsing the post-SPMD HLO
(``compiled.as_text()``): we sum the *output shape* bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op (a standard, slightly conservative proxy for per-chip link traffic).
"""

from __future__ import annotations

import dataclasses
import math
import re

PEAK_FLOPS = 197e12  # bf16 / chip (TPU v5e)
HBM_BW = 819e9  # B / s / chip
ICI_BW = 50e9  # B / s / link

_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.MULTILINE,
)

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every tensor literal in a shape string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype = m.group(1)
        dims = m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        b = _DTYPE_BYTES.get(dtype[:4], None) or _DTYPE_BYTES.get(dtype[:3], 4)
        if dtype.startswith("f8"):
            b = 1
        total += n * b
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-kind output bytes of collective ops in a post-SPMD HLO module.

    ``-start``/``-done`` pairs are counted once (the -start carries the op).
    """
    by_kind: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        # -done ops repeat the shape of their -start; skip them
        tail = hlo_text[m.end() - 1 : m.end() + 8]
        line = hlo_text[m.start():hlo_text.index("\n", m.start())]
        if f"{kind}-done" in line:
            continue
        by_kind[kind] = by_kind.get(kind, 0) + _shape_bytes(shape_str)
    return by_kind


@dataclasses.dataclass
class Roofline:
    flops_total: float
    hbm_bytes_total: float
    collective_bytes_per_chip: float
    chips: int

    @property
    def compute_s(self) -> float:
        return self.flops_total / (self.chips * PEAK_FLOPS)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes_total / (self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_chip / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops_total": self.flops_total,
            "hbm_bytes_total": self.hbm_bytes_total,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def from_compiled(compiled, chips: int) -> Roofline:
    """Build the roofline terms from a jax Compiled object.

    jax cost_analysis on the CPU backend reports metrics for the
    *per-device* partitioned module; totals are per-device x chips.
    """
    cost = compiled.cost_analysis()
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    coll_total = float(sum(coll.values()))
    return Roofline(
        flops_total=flops_dev * chips,
        hbm_bytes_total=bytes_dev * chips,
        collective_bytes_per_chip=coll_total,
        chips=chips,
    )


#: Keys a split-program dry-run result holds once per program.
PER_PROGRAM = ("compile_s", "memory_analysis", "collectives", "roofline")


def per_program(result: dict) -> list[dict]:
    """A dry-run result as one result per compiled program.  The FD-SVRG
    result keeps one entry per program under each :data:`PER_PROGRAM`
    key, and each of its parts names its program after the shape; any
    other result is one program."""
    rl = result.get("roofline")
    if not rl or "compute_s" in rl:
        return [result]
    return [
        {**result, "shape": f"{result['shape']}/{name}",
         **{k: result[k][name] for k in PER_PROGRAM if k in result}}
        for name in rl
    ]


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N·D per generated/scored token for
    inference (N = active params)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
