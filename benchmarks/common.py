"""Shared benchmark utilities: datasets, timing, claim checks."""
from __future__ import annotations

import functools
import os
import time
from typing import List, Optional

from repro.data import synthetic
from repro.obs import OBS_SCHEMA

# resolved FitConfig dict of every fit the suites run; benchmarks/run.py
# drains this into artifacts/bench/manifests.json. In-process fits are
# recorded automatically (run.py wraps api.fit); suites that fit in a
# SUBPROCESS (benchmarks/xl_engine.py needs forced host devices) call
# `record_manifest` themselves with the child's resolved configs.
MANIFESTS: List[dict] = []

#: bound families whose ``n_recomputed`` counts single (point, centroid)
#: distances (elkan's per-pair test, exponion's annulus); the others
#: count points scanned against all k centroids ("kscan")
PAIR_COUNTED_BOUNDS = ("elkan", "exponion")


def pair_dist_evals(n_recomputed: int, k: int, bounds: str) -> int:
    """``n_recomputed`` of a ``bounds`` fit in pair-distance evals."""
    n = max(0, int(n_recomputed))
    return n if bounds in PAIR_COUNTED_BOUNDS else n * k


def record_manifest(suite: str, config_dict: dict, *,
                    wall_s: Optional[float] = None,
                    obs: Optional[dict] = None,
                    kernel_plan: Optional[dict] = None,
                    nulls: Optional[dict] = None) -> None:
    """Record one run's manifest entry.

    Beyond the resolved config, each entry carries ``wall_s`` (end-to-
    end fit wall-clock), an ``obs`` per-round summary (rounds, total
    k-scans, retrace count, peak queue depth where a queue exists), the
    resolved ``kernel_plan`` the fit dispatched through (backend, block
    sizes, bucket — `repro.kernels.plan.KernelPlan.to_dict`) and the
    ``obs_schema`` version. Every null is EXPLAINED: the ``nulls``
    dict maps each absent field to the reason it is absent, so a
    manifest reader can distinguish "not measured" from "measured
    zero" — the old ``kernel_backend: null`` blind spot, made explicit.
    """
    reasons = dict(nulls or {})
    if wall_s is None:
        reasons.setdefault(
            "wall_s", "fit ran in a subprocess; the child's wall clock "
                      "was not captured")
    if obs is None:
        reasons.setdefault(
            "obs", "fit not driven through api.fit in this process — "
                   "no per-round summary collected")
    if kernel_plan is None:
        reasons.setdefault(
            "kernel_plan", "fit ran in a subprocess or predates the "
                           "dispatch plane — the resolved plan was not "
                           "surfaced on its FitOutcome")
    MANIFESTS.append({"suite": suite, "config": config_dict,
                      "obs_schema": OBS_SCHEMA, "wall_s": wall_s,
                      "obs": obs, "kernel_plan": kernel_plan,
                      "nulls": reasons})


#: label of every report a CPU-rehearsal child writes: its numbers come
#: from forced host devices, never from a chip.
CPU_REHEARSAL = {"platform": "cpu",
                 "note": "CPU rehearsal on forced host devices; "
                         "no number here is a device measurement"}


def cpu_child_env() -> dict:
    """Environment for a suite's child process that forces host devices.

    Pinned to ``JAX_PLATFORMS=cpu``: a chip belongs to one process, and
    the runner's own process may already hold it, so a child that
    reached for it would fail or hang.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


@functools.lru_cache(maxsize=None)
def dataset(name: str, quick: bool = False):
    """(X_train, X_val) stand-ins for the paper's two datasets."""
    if name == "infmnist":
        n = 20_000 if quick else 60_000
        X = synthetic.infmnist_like(n + n // 10, seed=0)
    elif name == "rcv1":
        n = 20_000 if quick else 60_000
        dim = 1024 if quick else 2048
        X = synthetic.rcv1_like(n + n // 10, dim=dim, seed=0)
    else:
        raise KeyError(name)
    return X[:n], X[n:]


def mse_at_times(telemetry, grid: List[float]) -> List[float]:
    """Validation MSE at each wall-time point (step function).

    Accepts `repro.api.Telemetry` records or legacy dict records.
    """
    recs = [t.to_dict() if hasattr(t, "to_dict") else t for t in telemetry]
    pts = [(t["t"], t["val_mse"]) for t in recs
           if t.get("val_mse") is not None]
    out = []
    for g in grid:
        best = None
        for t, v in pts:
            if t <= g:
                best = v
        out.append(best if best is not None else float("nan"))
    return out


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0


def check(name: str, ok: bool, detail: str = "") -> bool:
    print(f"  claim[{name}]: {'PASS' if ok else 'FAIL'} {detail}")
    return ok
