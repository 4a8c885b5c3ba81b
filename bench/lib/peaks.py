"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from the program's ``roofline/analysis.PEAKS`` so that a change
to the program cannot change the yardstick. A device missing from the
table has no roofline: `peaks_for` raises rather than borrow another
chip's numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py"
                       ) from None
