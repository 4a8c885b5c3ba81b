"""Kernel dispatch suite: price each kernel backend against the roofline.

One fit per backend ("ref", "pallas") on the same well-separated blobs,
each traced through `repro.obs` so the `fit_roofline_utilization` gauge
lands in the trace dir's metrics export — the per-backend utilization
the manifest records. Claim checks:

  * label parity — the Pallas fused round must produce labels
    bit-identical to the ref kernels (the dispatch plane's core
    contract, `scripts/smoke_kernels.py` proves it across engines);
  * every traced fit must surface a resolved `KernelPlan` on its
    outcome, and a utilization gauge exactly when its device has
    published peaks (`roofline.analysis.PEAKS`); otherwise the reason
    is recorded — no unexplained nulls.

Run standalone (`python -m benchmarks.kernels`) or via
`python -m benchmarks.run --suite kernels` (which additionally writes
the per-fit manifests, kernel plans included).
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

from benchmarks import common
from repro import api
from repro.roofline.analysis import peaks_for

ART = Path(__file__).resolve().parent.parent / "artifacts" / "bench"
BACKENDS = ("ref", "pallas")


def blobs(n: int, k: int, d: int, seed: int = 0):
    """Well-separated blobs: inter-center distances dwarf float32 ulp
    drift in the S->C path, so a correct kernel produces bit-equal
    labels, not merely close ones."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 12.0
    a = rng.integers(0, k, size=n)
    return (centers[a] + rng.normal(size=(n, d))).astype(np.float32)


def utilization_from(trace_dir: Path):
    vals = []
    for f in sorted(trace_dir.glob("metrics-p*.json")):
        g = json.loads(f.read_text()).get("gauges", {})
        if g.get("fit_roofline_utilization") is not None:
            vals.append(float(g["fit_roofline_utilization"]))
    return max(vals) if vals else None


def main(quick: bool = True):
    print("== Kernel dispatch: per-backend wall vs roofline ==")
    n = 4096 if quick else 65_536
    k, d = 16, 8
    X = blobs(n, k, d)
    results = {}
    for backend in BACKENDS:
        trace_dir = ART / f"trace-kernels-{backend}"
        trace_dir.mkdir(parents=True, exist_ok=True)
        for old in trace_dir.glob("metrics-p*.json"):
            old.unlink()
        with common.Timer() as t:
            out = api.fit(X, api.FitConfig(
                k=k, b0=max(2 * k, n // 16), seed=0, max_rounds=40,
                kernel_backend=backend, trace_dir=str(trace_dir)))
        util = utilization_from(trace_dir)
        results[backend] = {
            "wall_s": round(t.seconds, 3),
            "fit_roofline_utilization": util,
            "kernel_plan": out.kernel_plan,
            "labels": out.labels,
        }
        ustr = f"{util:.4f}" if util is not None else "None"
        plan = out.kernel_plan or {}
        print(f"  {backend:>6s}: wall {t.seconds:6.2f}s  "
              f"utilization {ustr}  plan "
              f"{plan.get('backend')}/bn={plan.get('bn')}"
              f"/bk={plan.get('bk')}/bd={plan.get('bd')}")

    ok = common.check(
        "pallas labels bit-equal to ref",
        bool(np.array_equal(results["pallas"]["labels"],
                            results["ref"]["labels"])))
    kind = jax.devices()[0].device_kind
    has_peaks = peaks_for(kind) is not None
    for backend in BACKENDS:
        if not has_peaks:
            results[backend]["nulls"] = {
                "fit_roofline_utilization":
                    f"no published peaks for device_kind {kind!r}"}
        ok &= common.check(
            f"{backend}: roofline utilization recorded iff the device "
            f"has peaks",
            (results[backend]["fit_roofline_utilization"] is not None)
            == has_peaks)
        ok &= common.check(
            f"{backend}: resolved kernel plan on the outcome",
            (results[backend]["kernel_plan"] or {}).get("backend")
            == backend)
    ART.mkdir(parents=True, exist_ok=True)
    report = {b: {kk: v for kk, v in r.items() if kk != "labels"}
              for b, r in results.items()}
    report["device"] = {"platform": jax.devices()[0].platform,
                        "kind": kind}
    (ART / "kernels.json").write_text(json.dumps(report, indent=1))
    return ok


if __name__ == "__main__":
    raise SystemExit(0 if main(quick=True) else 1)
