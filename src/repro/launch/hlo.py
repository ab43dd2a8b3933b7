"""HLO-text analysis: collective traffic + roofline terms.

``cost_analysis()`` gives HLO_FLOPs and HLO_bytes but NOT collective bytes;
those are extracted here by parsing the SPMD-partitioned module text
(``compiled.as_text()``), where every shape is a PER-DEVICE shape.  Per-op
link-traffic model (ring algorithms, N = replica-group size):

    all-reduce         2·S·(N−1)/N      (reduce-scatter + all-gather phases)
    all-gather         S·(N−1)/N        (S = output bytes, already gathered)
    reduce-scatter     S·(N−1)          (S = output bytes; input = N·S)
    all-to-all         S·(N−1)/N
    collective-permute S                (point-to-point)

Roofline terms (task spec; v5e constants):
    compute    = HLO_FLOPs / (chips · 197e12 FLOP/s)
    memory     = HLO_bytes / (chips · 819e9 B/s)
    collective = per-chip collective bytes / 50e9 B/s
                 (algebraically equal to total/(chips·link_bw) since SPMD
                  shapes are per-device)
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

__all__ = [
    "HW",
    "CollectiveStats",
    "parse_collectives",
    "RooflineTerms",
    "roofline_terms",
    "shape_bytes",
]


@dataclasses.dataclass(frozen=True)
class HW:
    """TPU v5e-class hardware constants (task spec)."""

    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # B/s per chip
    link_bw: float = 50e9            # B/s per ICI link
    hbm_bytes: float = 16e9


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}")
_GROUPS_ARR_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    if not dims:
        return b
    return b * int(np.prod([int(d) for d in dims.split(",") if d]))


def _result_bytes(line: str, op: str) -> int:
    """Sum the shape literals in the result type (LHS of the op name)."""
    eq = line.find(" = ")
    if eq < 0:
        return 0
    opi = line.find(op, eq)
    region = line[eq + 3 : opi if opi > 0 else None]
    return sum(shape_bytes(d, s) for d, s in _SHAPE_RE.findall(region))


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_ARR_RE.search(line)
    if m:  # replica_groups=[G,N] iota form: G groups of N
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].strip("{ ")
        n = len([x for x in first.split(",") if x.strip() != ""])
        return max(n, 1)
    return total_devices


@dataclasses.dataclass
class CollectiveStats:
    per_chip_bytes: float = 0.0
    op_bytes: dict = dataclasses.field(default_factory=dict)
    op_counts: dict = dataclasses.field(default_factory=dict)

    def add(self, op: str, traffic: float):
        self.per_chip_bytes += traffic
        self.op_bytes[op] = self.op_bytes.get(op, 0.0) + traffic
        self.op_counts[op] = self.op_counts.get(op, 0) + 1


def parse_collectives(hlo_text: str, total_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("//") or " = " not in ls:
            continue
        for op in _COLLECTIVES:
            # Match the op invocation (e.g. "all-reduce(" or "all-reduce-start(").
            if f" {op}(" in ls or f" {op}-start(" in ls:
                size = _result_bytes(ls, op)
                n = _group_size(ls, total_devices)
                if n <= 1:
                    continue
                if op == "all-reduce":
                    traffic = 2.0 * size * (n - 1) / n
                elif op == "all-gather":
                    traffic = size * (n - 1) / n
                elif op == "reduce-scatter":
                    traffic = size * (n - 1)
                elif op == "all-to-all":
                    traffic = size * (n - 1) / n
                else:  # collective-permute
                    traffic = float(size)
                stats.add(op, traffic)
                break
    return stats


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_accessed: float
    collective_bytes: float  # per chip

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self, model_flops: float, chips: int, hw: HW = HW()) -> float:
        """Useful-FLOPs throughput / peak, at the roofline step time."""
        if self.step_time_s == 0:
            return 0.0
        return model_flops / chips / self.step_time_s / hw.peak_flops


def roofline_terms(
    flops: float,
    bytes_accessed: float,
    collective_per_chip_bytes: float,
    chips: int,
    hw: HW = HW(),
) -> RooflineTerms:
    """flops/bytes are whole-program HLO totals; collectives are per-chip.

    On an SPMD program ``cost_analysis`` already reports per-device work, so
    callers pass ``chips=1`` for it.
    """
    return RooflineTerms(
        compute_s=flops / (chips * hw.peak_flops),
        memory_s=bytes_accessed / (chips * hw.hbm_bw),
        collective_s=collective_per_chip_bytes / hw.link_bw,
        flops=flops,
        bytes_accessed=bytes_accessed,
        collective_bytes=collective_per_chip_bytes,
    )
