"""Kernel dispatch suite: one fit per kernel backend, timed.

One fit per backend ("ref", "pallas") on the same well-separated blobs;
the manifest records each one's wall time and kernel plan. Claim checks:

  * label parity — the Pallas fused round must produce labels
    bit-identical to the ref kernels (the dispatch plane's core
    contract, `scripts/smoke_kernels.py` proves it across engines);
  * every fit must surface a resolved `KernelPlan` on its outcome.

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


def main(quick: bool = True):
    print("== Kernel dispatch: per-backend wall time ==")
    n = 4096 if quick else 65_536
    k, d = 16, 8
    X = blobs(n, k, d)
    results = {}
    for backend in BACKENDS:
        with common.Timer() as t:
            out = api.fit(X, api.FitConfig(
                k=k, b0=max(2 * k, n // 16), seed=0, max_rounds=40,
                kernel_backend=backend))
        results[backend] = {
            "wall_s": round(t.seconds, 3),
            "kernel_plan": out.kernel_plan,
            "labels": out.labels,
        }
        plan = out.kernel_plan or {}
        print(f"  {backend:>6s}: wall {t.seconds:6.2f}s  plan "
              f"{plan.get('backend')}/bn={plan.get('bn')}"
              f"/bk={plan.get('bk')}/bd={plan.get('bd')}")

    ok = common.check(
        "pallas labels bit-equal to ref",
        bool(np.array_equal(results["pallas"]["labels"],
                            results["ref"]["labels"])))
    for backend in BACKENDS:
        ok &= common.check(
            f"{backend}: resolved kernel plan on the outcome",
            (results[backend]["kernel_plan"] or {}).get("backend")
            == backend)
    ART.mkdir(parents=True, exist_ok=True)
    report = {b: {kk: v for kk, v in r.items() if kk != "labels"}
              for b, r in results.items()}
    report["device"] = {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind}
    (ART / "kernels.json").write_text(json.dumps(report, indent=1))
    return ok


if __name__ == "__main__":
    raise SystemExit(0 if main(quick=True) else 1)
