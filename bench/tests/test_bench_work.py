"""The fit's needed-work count, on rounds computed by hand."""
from collections import namedtuple
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
from bench.lib.peaks import PEAKS, peaks_for
from bench.lib.registry import load_module

work = load_module(Path(bench_tiny.ROOT) / "bench" / "metrics"
                   / "_fit_work.py")
Round = namedtuple("Round", "b n_changed n_recomputed")


def test_round_work_by_hand():
    k, d = 4, 10
    rounds = [Round(100, 0, 100),   # first round: all 100 rows are new
              Round(100, 7, 20),    # settled rows need no distances
              Round(200, 3, 130)]   # the batch doubled: 100 new rows
    got = work.round_work(rounds, k, d)
    # ops: 2kd per recomputed row + d per new row + 2d per changed row
    #      + kd for the update
    assert got[0][0] == 2 * 4 * 10 * 100 + 10 * 100 + 40
    assert got[1][0] == 2 * 4 * 10 * 20 + 10 * 2 * 7 + 40
    assert got[2][0] == 2 * 4 * 10 * 130 + 10 * (100 + 2 * 3) + 40
    # bytes: 4d per recomputed row + 20 per active row + 4 per
    #        recomputed label + the 4kd centroid block
    assert got[0][1] == 40 * 100 + 20 * 100 + 4 * 100 + 160
    assert got[1][1] == 40 * 20 + 20 * 100 + 4 * 20 + 160
    assert got[2][1] == 40 * 130 + 20 * 200 + 4 * 130 + 160


def test_least_time_takes_the_larger_term_per_round():
    peaks = peaks_for("TPU v5 lite")
    k, d = 50, 784
    # a dense round at b = 400,000 is bound by bytes
    dense = [Round(400_000, 0, 400_000)]
    (ops, nbytes), = work.round_work(dense, k, d)
    t, compute = work.least_time_s(dense, k, d, peaks)
    assert t == pytest.approx(nbytes / 819e9)
    assert compute == 0.0
    assert ops / 197e12 < t
    # at k = 8192 the same rows are bound by operations
    t, compute = work.least_time_s(dense, 8192, d, peaks)
    assert compute == 1.0
    assert t == pytest.approx(work.round_work(dense, 8192, d)[0][0]
                              / 197e12)


def test_padding_and_implementation_do_not_count():
    # the count depends on k, not on the 128 lanes k=50 runs in, and
    # not on how many rows a kernel touched
    a = work.round_work([Round(1000, 5, 10)], 50, 784)
    b = work.round_work([Round(1000, 5, 10)], 128, 784)
    assert a[0][0] < b[0][0]
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError):
        peaks_for("cpu")
