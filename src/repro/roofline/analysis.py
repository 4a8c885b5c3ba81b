"""Three-term roofline from a compiled dry-run artifact.

  compute term    = HLO_FLOPs / peak_FLOP/s          (per device)
  memory term     = HLO_bytes / HBM_bw               (per device)
  collective term = wire_bytes / link_bw             (per device)

HLO FLOPs / bytes come from ``compiled.cost_analysis()`` of the
POST-PARTITIONING module, i.e. they are already per-device. Collective
bytes are not in cost_analysis: we parse the partitioned HLO text and sum
estimated *wire* volume per op (ring algorithms, large-n approximation):

  all-gather        out_bytes              (each device receives ~out)
  reduce-scatter    in_bytes               (each device sends ~in)
  all-reduce        2 * out_bytes          (RS + AG phases)
  all-to-all        out_bytes
  collective-permute out_bytes             (one hop)

Hardware model: the published peaks of one chip, keyed by the
``device_kind`` JAX reports (`PEAKS`). A device missing from the table
has no roofline: callers get None from `peaks_for` and say so, rather
than borrowing another chip's numbers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """One chip's published peaks."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    link_bw: float      # bytes/s of one ICI link
    source: str


#: ``device_kind`` as JAX reports it for a TPU v5e.
V5E = "TPU v5 lite"

#: Published per-chip peaks by ``device_kind``.
PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI (one of four "
               "links counted: 50 GB/s)"),
}


def peaks_for(device_kind: Optional[str]) -> Optional[ChipPeaks]:
    """The chip's peaks, or None when its ``device_kind`` is not in
    `PEAKS` (the CPU among them)."""
    return PEAKS.get(device_kind or "")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "e4m3": 1, "e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_shapes(text: str):
    return [_shape_bytes(m.group(1), m.group(2))
            for m in _SHAPE_RE.finditer(text)]


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    def add(self, kind: str, b: float):
        self.wire_bytes += b
        self.by_kind[kind] = self.by_kind.get(kind, 0.0) + b
        self.counts[kind] = self.counts.get(kind, 0) + 1


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum estimated wire bytes of every collective in partitioned HLO.

    Handles both sync ops and async `-start` forms (the `-done` halves
    carry no payload and are skipped). Shapes in post-SPMD HLO are
    per-device shapes, so the result is per-device wire volume.
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1]
        m = re.search(r"\b([a-z0-9-]+)\(", rhs)
        if not m:
            continue
        op = m.group(1)
        base = op.removesuffix("-start")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        shapes = _line_shapes(rhs)
        if not shapes:
            continue
        # The largest shape on the line is the full buffer being moved in
        # every case (AG output, RS input, AR in==out) — robust to the
        # tuple-shaped async `-start` forms.
        full = float(max(shapes))
        stats.add(base, 2.0 * full if base == "all-reduce" else full)
    return stats


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    peaks: ChipPeaks
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None

    def step_time_s(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> Optional[float]:
        """useful FLOPs / (chips-seconds at peak) — the MFU-style score."""
        if not self.model_flops:
            return None
        t = self.step_time_s()
        return (self.model_flops / self.peaks.flops) / t if t > 0 else None


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float, *,
                   peaks: ChipPeaks,
                   model_flops: Optional[float] = None) -> Roofline:
    c = flops / peaks.flops
    m = hbm_bytes / peaks.hbm_bw
    x = wire_bytes / peaks.link_bw
    dom = max((c, "compute"), (m, "memory"), (x, "collective"))[1]
    useful = (model_flops / flops) if (model_flops and flops) else None
    return Roofline(flops=flops, hbm_bytes=hbm_bytes, wire_bytes=wire_bytes,
                    compute_s=c, memory_s=m, collective_s=x,
                    bottleneck=dom, peaks=peaks, model_flops=model_flops,
                    useful_ratio=useful)


def model_flops_train(active_params: int, tokens: int) -> float:
    """6 N D (fwd 2ND + bwd 4ND), MoE: N = active params."""
    return 6.0 * active_params * tokens


def model_flops_fwd(active_params: int, tokens: int) -> float:
    return 2.0 * active_params * tokens
