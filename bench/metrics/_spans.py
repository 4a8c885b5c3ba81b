"""The program's own ``repro.*`` spans in a trace, and the device time
under each.

The program opens them on the profiler's host plane
(``jax.profiler.TraceAnnotation``): ``repro.fit.begin`` around a fit's
set-up, ``repro.round`` around each round of the host loop with
``repro.round.info`` inside it, and ``repro.predict.put`` /
``.dispatch`` / ``.fetch`` inside each ``repro.predict``. A program
that opens none (an older commit) leaves these lists empty, and each
reader then returns nothing.
"""
from __future__ import annotations

from bisect import bisect_left, bisect_right
from typing import List


def spans_in(trace, name: str, window) -> list:
    """The host spans called ``name`` that lie wholly inside ``window``,
    in time order."""
    lo, hi = window
    return [e for e in trace.spans(name)
            if e.start_ns >= lo and e.end_ns <= hi]


def busy_each(trace, spans) -> List[float]:
    """Device busy ns under each span: the union of device-op intervals
    clipped to it, averaged over the chips that ran any op, as
    ``Trace.busy_ns`` takes it for one window (here in one pass over
    the ops, not one per span)."""
    chips = trace.chips()
    out = [0.0] * len(spans)
    for chip in chips:
        merged = []          # disjoint busy intervals, in time order
        for e in trace.device_ops:
            if e.where != chip:
                continue
            if merged and e.start_ns <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e.end_ns)
            else:
                merged.append([e.start_ns, e.end_ns])
        starts = [a for a, _ in merged]
        ends = [b for _, b in merged]
        for i, s in enumerate(spans):
            j0 = bisect_right(ends, s.start_ns)
            j1 = bisect_left(starts, s.end_ns)
            out[i] += sum(min(ends[j], s.end_ns) - max(starts[j], s.start_ns)
                          for j in range(j0, j1))
    return [b / len(chips) for b in out] if chips else out
