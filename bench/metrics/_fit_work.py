"""The work a nested mini-batch k-means fit needs, round by round.

Counted from each round's ``Telemetry`` (``b``, ``n_changed``,
``n_recomputed``) and the shape (k, d), never from what a kernel does,
so that a change that fuses, pads or reorders kernels leaves the count
as it is. Per round:

  operations
    distances       2 k d per recomputed row: one multiply-add per
                    feature against each of the k centroids. Rows the
                    bounds settled need none. Padding (k=50 runs as 128
                    lanes) is not work.
    delta S/v       d adds per new row (it joins a cluster), 2 d per
                    changed row (it leaves one and joins another).
    update          d per centroid: the division S / v.
  bytes
    rows            one f32 read of every recomputed row (changed and
                    new rows are among them): 4 d each.
    bound state     the label and two bounds of every active row, read
                    (12 bytes), the two bounds written back (8 bytes),
                    and the label of every recomputed row (4 bytes).
    centroids       the (k, d) f32 block once.

New rows in a round are those by which ``b`` grew since the round before
(all of ``b`` in the first round). The least time of a round is the
larger of operations over the chip's peak FLOP/s and bytes over its peak
bandwidth; a fit's is the sum over its rounds.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple


def round_work(rounds: Iterable, k: int, d: int
               ) -> List[Tuple[float, float]]:
    """(operations, bytes) of each round, in order."""
    out, b_prev = [], 0
    for r in rounds:
        b, changed, rec = int(r.b), int(r.n_changed), int(r.n_recomputed)
        new = max(0, b - b_prev)
        b_prev = b
        ops = 2.0 * k * d * rec + d * (new + 2 * changed) + k * d
        nbytes = 4.0 * d * rec + 20.0 * b + 4.0 * rec + 4.0 * k * d
        out.append((ops, nbytes))
    return out


def least_time_s(rounds: Iterable, k: int, d: int, peaks
                 ) -> Tuple[float, float]:
    """(least seconds for the whole fit, share of them in rounds whose
    operations, not bytes, bound the time)."""
    total = compute = 0.0
    for ops, nbytes in round_work(rounds, k, d):
        t_ops, t_bytes = ops / peaks.flops, nbytes / peaks.hbm_bw
        total += max(t_ops, t_bytes)
        if t_ops > t_bytes:
            compute += t_ops
    return total, (compute / total if total else 0.0)


def fit_rounds(obs) -> list:
    """The rounds of the traced fit(s), ``Telemetry`` records."""
    return [r for rec in obs.driver.records for r in rec.telemetry]
