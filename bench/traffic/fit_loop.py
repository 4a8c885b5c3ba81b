"""Traffic kind ``fit_loop``: whole fits, back to back, on one dataset.

Set-up makes the configuration's rows on the device from the seed, pulls
them to the host (``NestedKMeans.fit`` takes host rows and copies them
to the device, as a user's call does) and runs one warm-up fit, which
compiles or loads from the cache every (b, capacity) bucket the window
will use. Every fit of a run makes the same work.

How many rounds a fit takes depends on its data and its shuffle: on a
TPU v5 lite, 162 to 350 rounds over nine seeds at n=400,000, k=50, while
two fits of one seed agreed within 3%. So a mix may fix both
(``fixed_seeds``: ``{"data": ..., "fit": ...}``) and every run then fits
the same dataset in the same order, whatever its ``--seed``. Without
``fixed_seeds`` the run's seed makes the data and seeds the fit.

The window starts fits back to back until ``seconds`` have passed; each
fit that starts before then runs to its end. ``fit_s`` is the total
wall time of those fits over their count, each timed from the call
until the centroids and labels are on the host. A traced run traces
one whole fit.

Traffic parameters (``traffic/<mix>.json``): ``fixed_seeds`` (optional).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
import traceback
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class FitRecord:
    wall_s: float
    telemetry: list
    C: Optional[np.ndarray] = None
    labels: Optional[np.ndarray] = None
    error: Optional[str] = None

    def digest(self) -> str:
        h = hashlib.sha1(self.C.tobytes())
        h.update(self.labels.tobytes())
        return h.hexdigest()


class FitLoop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.X: Optional[np.ndarray] = None
        self.records: List[FitRecord] = []

    def seed(self, what: str) -> int:
        fixed = self.ctx.cell.traffic.get("fixed_seeds", {})
        return int(fixed.get(what, self.ctx.seed))

    def fit_config(self):
        from repro.api import FitConfig
        return FitConfig.from_dict({**self.config["fit"],
                                    "k": int(self.config["k"]),
                                    "seed": self.seed("fit")})

    def setup(self) -> None:
        import jax
        cfg = self.config
        data = cfg["data"]
        gen = self.ctx.registry.data(data["generator"])
        rows = gen.make(self.seed("data"), int(cfg["n"]), int(cfg["d"]),
                        **data.get("params", {}))
        self.X = np.asarray(jax.device_get(rows))
        del rows
        self._fit_config = self.fit_config()
        warm = self.warm = self.fit_once()
        if warm.error:
            raise RuntimeError(f"warm-up fit failed:\n{warm.error}")
        self.ctx.log(f"warm-up fit: {len(warm.telemetry)} rounds, "
                     f"{warm.wall_s:.3f} s")

    def fit_once(self) -> FitRecord:
        from repro.api import NestedKMeans
        t0 = time.perf_counter()
        try:
            km = NestedKMeans(self._fit_config).fit(self.X)
            C = np.array(km.cluster_centers_, np.float32)
            labels = np.array(km.labels_, np.int32)
        except Exception:            # a failed fit is counted, not fatal
            return FitRecord(time.perf_counter() - t0, [],
                             error=traceback.format_exc())
        wall = time.perf_counter() - t0
        return FitRecord(wall, list(km.telemetry_), C, labels)

    def window(self, seconds: float, traced: bool) -> Dict[str, Any]:
        import jax
        self.records = []
        deadline = time.perf_counter() + seconds
        while not self.records or (not traced
                                   and time.perf_counter() < deadline):
            with jax.profiler.TraceAnnotation("bench.fit"):
                rec = self.fit_once()
            self.records.append(rec)
            if rec.error:
                self.ctx.log(f"fit failed:\n{rec.error}")
        walls = [r.wall_s for r in self.records]
        return {"fit_s": sum(walls) / len(walls)}

    @property
    def attempted(self) -> int:
        return len(self.records)

    def check(self, limits: Dict[str, float]):
        """(failed fits, {number: worst value}) by the reference."""
        ref = self.ctx.registry.reference(self.config["reference"])
        worst: Dict[str, float] = {}
        verdict: Dict[str, bool] = {}
        failed = 0
        for rec in self.records:
            if rec.error:
                failed += 1
                continue
            key = rec.digest()
            if key not in verdict:
                nums = ref.check_fit(self.X, rec.C, rec.labels)
                ok = True
                for name, limit in limits.items():
                    # fmax would drop a NaN; a NaN has to stay in sight
                    worst[name] = float(np.max([worst.get(name, -np.inf),
                                                nums[name]]))
                    ok &= nums[name] <= limit
                verdict[key] = ok
            failed += not verdict[key]
        return failed, worst


def control(driver: FitLoop) -> None:
    """Put the reference's control in the program's place: a Lloyd fit
    at the next precision down, from the fit's own first centroids (the
    first k rows of its shuffle), as the window's one answer."""
    ref = driver.ctx.registry.reference(driver.config["reference"])
    k = int(driver.config["k"])
    order = np.random.default_rng(driver.seed("fit")).permutation(
        len(driver.X))
    t0 = time.perf_counter()
    C, labels, iters = ref.lloyd_fit(driver.X, driver.X[order[:k]])
    driver.ctx.log(f"control: Lloyd at bfloat16, {iters} "
                   f"iterations, {time.perf_counter() - t0:.1f} s")
    driver.records = [FitRecord(time.perf_counter() - t0, [], C, labels)]


def make(ctx) -> FitLoop:
    return FitLoop(ctx)
