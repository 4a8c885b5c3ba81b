"""Benchmark entry point: ``python -m benchmarks.run [--full]``.

One module per paper table/figure + the pruning study + the dry-run
roofline summary + the serving-latency study (`repro.serve`). Exit
code 0 iff every qualitative claim check passes.

Every `api.fit` a suite executes is recorded: the RESOLVED
`FitConfig.to_dict()` manifest of each run is written to
``artifacts/bench/manifests.json``, so any number in any table can be
reproduced with `FitConfig.from_dict` + the same dataset.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ART = Path(__file__).resolve().parent.parent / "artifacts" / "bench"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets / longer budgets")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig2,table1,table2,pruning,"
                         "roofline,serve,kernels,xl,multihost,outofcore,"
                         "obs")
    ap.add_argument("--suite", dest="only",
                    help="alias for --only")
    args = ap.parse_args()
    quick = not args.full

    from repro.util.env import enable_compile_cache
    enable_compile_cache()

    # record the exact FitConfig of every fit the suites run, plus its
    # wall clock and a per-round obs summary (k-scans off the telemetry,
    # jit traces off the tracecount hooks scoped to this one fit)
    from benchmarks import common
    from repro import api
    from repro.util import tracecount
    manifests = common.MANIFESTS
    current = {"suite": None}
    orig_fit = api.fit

    def recording_fit(X, config, **kw):
        tc0 = tracecount.snapshot()
        t0 = time.perf_counter()
        out = orig_fit(X, config, **kw)
        wall = time.perf_counter() - t0
        cfg = out.config
        # n_recomputed's unit depends on the bound family (kscan vs
        # pair) — record the family, the unit, and the unit-converted
        # pair-distance total so manifests compare across families.
        from repro.api.config import bound_state_bytes
        n_rec_total = int(sum(r.n_recomputed for r in out.telemetry))
        obs = {
            "rounds": len(out.telemetry),
            "kscans_total": n_rec_total,
            "bounds_family": cfg.bounds,
            "work_unit": ("pair" if cfg.bounds
                          in common.PAIR_COUNTED_BOUNDS else "kscan"),
            "pair_dist_evals": common.pair_dist_evals(
                n_rec_total, cfg.k, cfg.bounds),
            "bound_state_bytes": bound_state_bytes(
                cfg.bounds, len(X), cfg.k),
            "retrace_count": int(sum(tracecount.diff(tc0).values())),
            "peak_queue_depth": None,
        }
        nulls = {"peak_queue_depth":
                 "batch fit — no ingest queue in the path (the serve "
                 "suite records its queue's high-water mark)"}
        common.record_manifest(
            current["suite"], out.config.to_dict(),
            wall_s=round(wall, 3), obs=obs,
            kernel_plan=getattr(out, "kernel_plan", None), nulls=nulls)
        return out

    api.fit = recording_fit

    from benchmarks import (fig1_mse_vs_time, fig2_rho_effect, kernels,
                            multihost, obs_overhead, outofcore,
                            pruning_effectiveness, roofline_report,
                            serve_latency, table1_throughput,
                            table2_final_quality, xl_engine)
    suites = {
        "table1": table1_throughput.main,
        "fig1": fig1_mse_vs_time.main,
        "fig2": fig2_rho_effect.main,
        "table2": table2_final_quality.main,
        "pruning": pruning_effectiveness.main,
        "roofline": roofline_report.main,
        "serve": serve_latency.main,
        "kernels": kernels.main,
        "xl": xl_engine.main,
        "multihost": multihost.main,
        "outofcore": outofcore.main,
        "obs": obs_overhead.main,
    }
    chosen = (args.only.split(",") if args.only else list(suites))
    ok = True
    try:
        for name in chosen:
            current["suite"] = name
            t0 = time.time()
            res = suites[name](quick=quick)
            ok &= bool(res)
            print(f"[{name}] {'ok' if res else 'CLAIM-CHECK-FAILED'} "
                  f"({time.time() - t0:.0f}s)\n")
    finally:
        api.fit = orig_fit
        if manifests:
            ART.mkdir(parents=True, exist_ok=True)
            (ART / "manifests.json").write_text(json.dumps(
                {"quick": quick, "runs": manifests}, indent=1))
            print(f"wrote {len(manifests)} FitConfig manifests to "
                  f"{ART / 'manifests.json'}")
    print(f"benchmarks: {'ALL CLAIMS PASS' if ok else 'SOME CLAIMS FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
