"""Roofline-aware efficiency: achieved work per round vs the bound.

The natural unit of work depends on the bound family, and
``RoundInfo.n_recomputed`` is counted in that family's unit:

  * ``unit="kscan"`` (bounds none / hamerly2): one point scanned
    against all ``k`` centroids — n_recomputed counts the points whose
    bounds failed and paid a full distance pass (the quantity Newling &
    Fleuret's bounds papers track as *the* scaling signal).
  * ``unit="pair"`` (bounds elkan / exponion): one (point, centroid)
    pair distance — these families prune WITHIN the row (elkan's
    per-pair bound test, exponion's annular candidate set), so pricing
    their counter as full k-scans would overstate the work by the very
    factor the family exists to save.

From ``(k, d)`` the costs are

  * FLOPs:      ``3 * d`` per pair distance (one fused mul-add +
                 compare per dim; a k-scan is ``k`` pairs);
  * HBM bytes:  ``4 * d``  per scanning point (stream the f32 row
                 once; the centroid block is k*d*4 ONCE per round, not
                 per point). In pair units the row stream is estimated
                 at one row per ``k`` pairs — exact when rows scan the
                 full k, an overestimate (conservative bound) when the
                 annulus is small.

`WorkModel` prices a round with ``roofline/analysis.roofline_terms``
against the peaks of the fit's own chip (``device_kind``) and turns the
measured wall time into a **utilization** fraction — achieved /
attainable, given the round's own arithmetic intensity. A device with no
published peaks (the CPU among them) gets no roofline and no
utilization: `WorkModel.no_roofline` says why.

Plain Python + the jax-free roofline module — safe to import anywhere,
including inside the transfer-guarded host loop.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.roofline.analysis import Roofline, peaks_for, roofline_terms

#: FLOPs per (point, centroid, dim): diff, square (fused mul-add), and
#: the running-min compare amortised across dims.
FLOPS_PER_DIST = 3.0

#: bytes per f32 element streamed from memory.
F32_BYTES = 4


#: bound family -> the unit its ``n_recomputed`` counter is measured in
BOUNDS_WORK_UNIT = {
    "none": "kscan",
    "hamerly2": "kscan",
    "elkan": "pair",
    "exponion": "pair",
}


@dataclasses.dataclass(frozen=True)
class RoundWork:
    """Priced work of one round: counts, the bound, and utilization."""
    kscans: int            # full-k-scan equivalents (exact in kscan
                           # units; ceil(pairs / k) in pair units)
    dist_evals: int        # (point, centroid) pair distance evals
    flops: float
    hbm_bytes: float
    bound_s: Optional[float]     # roofline lower bound for this work;
                                 # None without the chip's peaks
    bottleneck: Optional[str]    # "compute" | "memory" | "collective"
    dt_s: Optional[float] = None
    utilization: Optional[float] = None   # bound_s / dt_s, in [0, ~1]
    unit: str = "kscan"    # what n_recomputed counted ("kscan" | "pair")


class WorkModel:
    """Prices nested rounds for a fixed ``(k, d)`` problem shape.

    ``unit`` declares what the rounds' ``n_recomputed`` counts:
    "kscan" (none/hamerly2 — points times full k) or "pair"
    (elkan/exponion — individual pair distances). Use `for_bounds` to
    pick the unit from a fit's bound family. ``device_kind`` picks the
    peaks the roofline uses; without published peaks for it, rounds are
    priced in operations and bytes only.
    """

    def __init__(self, k: int, d: int, unit: str = "kscan", *,
                 device_kind: Optional[str] = None):
        if k < 1 or d < 1:
            raise ValueError(f"WorkModel needs k, d >= 1, got k={k} d={d}")
        if unit not in ("kscan", "pair"):
            raise ValueError(f"unknown work unit {unit!r}")
        self.k = int(k)
        self.d = int(d)
        self.unit = unit
        self.peaks = peaks_for(device_kind)
        #: why rounds carry no roofline, or None when they do
        self.no_roofline = (
            None if self.peaks is not None else
            f"no published peaks for device_kind {device_kind!r}; "
            f"roofline and utilization not computed")

    @classmethod
    def for_bounds(cls, k: int, d: int, bounds: str, *,
                   device_kind: Optional[str] = None) -> "WorkModel":
        """The model whose unit matches a bound family's counter."""
        return cls(k, d, unit=BOUNDS_WORK_UNIT.get(bounds, "kscan"),
                   device_kind=device_kind)

    def pair_evals(self, n_recomputed: int) -> int:
        """``n_recomputed`` converted to pair-distance evaluations."""
        n = max(0, int(n_recomputed))
        return n * self.k if self.unit == "kscan" else n

    def flops(self, n_recomputed: int) -> float:
        return FLOPS_PER_DIST * self.d * self.pair_evals(n_recomputed)

    def hbm_bytes(self, n_recomputed: int) -> float:
        # each scanning row streams once; the centroid block streams
        # once per round regardless of how many points scan it. In pair
        # units the row count is estimated at ceil(pairs / k) — exact
        # for full-row scans, conservative for small annuli.
        n = max(0, int(n_recomputed))
        rows = n if self.unit == "kscan" else -(-n // self.k)
        return F32_BYTES * (rows * self.d + self.k * self.d)

    def roofline(self, n_recomputed: int) -> Roofline:
        if self.peaks is None:
            raise ValueError(self.no_roofline)
        return roofline_terms(self.flops(n_recomputed),
                              self.hbm_bytes(n_recomputed), 0.0,
                              peaks=self.peaks)

    def round_work(self, n_recomputed: int,
                   dt_s: Optional[float] = None) -> RoundWork:
        """Price a round; with ``dt_s`` and the chip's peaks also
        compute utilization."""
        n = max(0, int(n_recomputed))
        bound = bottleneck = util = None
        if self.peaks is not None:
            rl = self.roofline(n)
            bound, bottleneck = rl.step_time_s(), rl.bottleneck
            if dt_s is not None and dt_s > 0.0:
                util = bound / dt_s
        kscans = n if self.unit == "kscan" else -(-n // self.k)
        return RoundWork(kscans=kscans, dist_evals=self.pair_evals(n),
                         flops=self.flops(n), hbm_bytes=self.hbm_bytes(n),
                         bound_s=bound, bottleneck=bottleneck,
                         dt_s=dt_s, utilization=util, unit=self.unit)
