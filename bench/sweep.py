"""Sweep the offered rate of an open-loop predict cell to find its knee.

    python bench/sweep.py --workload <cell> --seeds N1 N2 ... --seconds S
        --rates R1 R2 ...

One process, one set-up (with the first seed); then, for each rate, one
window per seed, each with the cell's own traffic mix at that rate.
Prints one JSON line per window: p50 and p95 latency, how late the
generator ran, the answered rate, the mean latency of the first and the
last tenth of the requests (a backlog that grows through the window
shows as the second far above the first), and what a stall looks like:
the longest service of one request and the generator's latest hand-off,
and each run of requests held up by a pause; and the latency of each
request size apart. A request's service runs from when the server could
take it (handed over, and the one before done) until its labels are on
the host.
Not part of a benchmark run: it is how the rate in the cell's traffic
file was chosen, at about four fifths of the highest rate sustained.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench.lib.chip import process_age_s, require_chip  # noqa: E402
from bench.lib.registry import Registry  # noqa: E402
from bench.run import Context, log  # noqa: E402

STALL_MS = 20.0     # a service or a hand-off this long is listed


def window_stats(lg, t_start: float) -> dict:
    lat = lg.latency_ms()
    tenth = max(1, len(lat) // 10)
    done = lg.done[np.isfinite(lg.done)]
    late = (lg.sent - lg.due) * 1e3
    prev_done = np.concatenate([[lg.sent[0]], lg.done[:-1]])
    service = (lg.done - np.fmax(lg.sent, prev_done)) * 1e3
    # a stall: a run of requests served or handed over STALL_MS late,
    # none of them more than a second after the one before
    hit = np.flatnonzero((np.nan_to_num(service) > STALL_MS)
                         | (np.nan_to_num(late) > STALL_MS))
    stalls = []
    for group in np.split(hit, np.flatnonzero(
            np.diff(lg.due[hit]) > 1.0) + 1) if len(hit) else []:
        stalls.append({
            "at_s": float(lg.due[group[0]] - lg.due[0]),
            "requests": len(group),
            "service_max_ms": float(np.nanmax(service[group])),
            "late_max_ms": float(np.nanmax(late[group]))})
    return {
        # nearest rank, as the driver takes p50
        "predict_p95_ms": float(np.percentile(lat, 95,
                                              method="inverted_cdf")),
        "process_age_s": t_start,
        "late_p95_ms": float(np.nanpercentile(late, 95)),
        "late_max_ms": float(np.nanmax(late)),
        "service_max_ms": float(np.nanmax(service)),
        "answered_per_s": len(done) / (done.max() - lg.due[0]),
        "first_tenth_mean_ms": float(np.mean(lat[:tenth])),
        "last_tenth_mean_ms": float(np.mean(lat[-tenth:])),
        "unanswered": int(np.sum(~np.isfinite(lat))),
        "by_size": {int(z): {"n": int(np.sum(lg.sizes == z)),
                             **{f"p{q}_ms": float(np.percentile(
                                 lat[lg.sizes == z], q))
                                for q in (50, 95, 100)}}
                    for z in np.unique(lg.sizes)},
        "n_stalls": len(stalls),
        "stalls": stalls[:8],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    registry = Registry(BENCH.parent)
    cell = registry.cell(args.workload)
    require_chip(cell.chips)
    from repro.util.env import enable_compile_cache
    enable_compile_cache()
    ctx = Context(cell=cell, seed=args.seeds[0], registry=registry, log=log)
    driver = registry.driver(cell).make(ctx)
    driver.setup()
    gc.collect()
    gc.freeze()
    for rate in args.rates:
        for seed in args.seeds:
            driver.p = dict(driver.p, rate_per_s=rate)
            driver.ctx = ctx = Context(cell=cell, seed=seed,
                                       registry=registry, log=log)
            age = process_age_s()
            m = driver.window(args.seconds, traced=False)
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, **m,
                **window_stats(driver.log, age)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
