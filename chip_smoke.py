"""Run the nested k-means fit on a TPU through the user entry points.

    python chip_smoke.py             # one chip: fit, reference fit, predict
    python chip_smoke.py --chips 4   # four chips: mesh and XL fits vs local

One chip: `NestedKMeans(FitConfig(...)).fit(X)` at the paper's infMNIST
configuration (n=400,000, d=784, k=50, tb, rho=inf, b0=5000, hamerly2)
on data made from ``--seed``, through the Pallas kernels; then
`CodebookSnapshot.predict` on requests of 1, 256 and 2048 rows. Checked
against independent references: the held-out MSE (host, float64) of a
``kernel_backend="ref"`` fit run at ``highest`` matmul precision, within
1%, and a float64 host nearest-centroid search, on >= 99% of rows.

Four chips: the same fit on ``backend="mesh"`` over 4 data shards and
on ``backend="xl"`` over a (data=2, model=2) mesh, each compared with
the one-chip local fit; their data and centroid buffers must sit on 4
distinct devices.

Exits nonzero, with no result line, when JAX finds no TPU. Otherwise
the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Compiles go to the persistent cache (`repro.util.env.enable_compile_cache`),
so a second run in the same checkout reads them back.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N, D, K, B0 = 400_000, 784, 50, 5000
N_HELD = 4096
REQUEST_ROWS = (1, 256, 2048)
MSE_RTOL = 0.01          # the paper's own quality target
MIN_AGREEMENT = 0.99
MAX_ROUNDS = 1000


class CompileLog:
    """Backend-compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.compile_s, self.hits, self.misses)

    def since(self, snap):
        c, h, m = snap
        return {"compile_s": self.compile_s - c, "cache_hits": self.hits - h,
                "cache_writes": self.misses - m}


def require_tpu():
    """The device, or exit 2: this script never falls back to the CPU."""
    import jax
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        dev, err = None, e
    if dev is None or dev.platform != "tpu":
        found = dev.platform if dev is not None else f"none ({err})"
        print(f"chip_smoke: no TPU found (JAX platform: {found}); "
              f"this smoke runs only on a TPU", file=sys.stderr)
        sys.exit(2)
    return dev


def make_data(seed: int):
    from repro.data.synthetic import infmnist_like
    return infmnist_like(N, seed=seed), infmnist_like(N_HELD, seed=seed + 1)


def base_config(seed: int, **kw):
    from repro.api import FitConfig
    return FitConfig(k=K, algorithm="tb", rho=float("inf"), b0=B0,
                     bounds="hamerly2", seed=seed, max_rounds=MAX_ROUNDS,
                     **kw)


def nearest_f64(X, C):
    """Float64 host reference: (labels, squared distances)."""
    X = np.asarray(X, np.float64)
    C = np.asarray(C, np.float64)
    d2 = ((X * X).sum(1)[:, None] - 2.0 * X @ C.T + (C * C).sum(1)[None])
    a = np.argmin(d2, axis=1)
    return a, np.maximum(d2[np.arange(len(X)), a], 0.0)


def held_mse(C, X_held) -> float:
    C = np.asarray(C)
    if not np.all(np.isfinite(C)):
        raise AssertionError("centroids hold a non-finite value")
    return float(nearest_f64(X_held, C)[1].mean())


def fit(X, X_held, cfg, *, mesh=None):
    """One `NestedKMeans.fit`: (estimator, wall seconds)."""
    from repro.api import NestedKMeans
    t0 = time.perf_counter()
    km = NestedKMeans(cfg, mesh=mesh).fit(X, X_val=X_held)
    return km, time.perf_counter() - t0


def fit_summary(km, wall_s):
    tel = km.telemetry_
    return {"rounds": len(tel), "final_b": max(r.b for r in tel),
            "converged": bool(km.converged_), "wall_s": wall_s}


def check_plan(plan):
    want = {"backend": "pallas", "interpret": False, "source": "table"}
    got = {key: (plan or {}).get(key) for key in want}
    if got != want:
        raise AssertionError(f"kernel plan {plan} is not {want}")


def check_fit(km, name):
    """The fit ran on compiled Pallas kernels and reached b = n."""
    check_plan(km.outcome_.kernel_plan)
    final_b = max(r.b for r in km.telemetry_)
    if final_b != N:
        raise AssertionError(f"{name} fit stopped at b={final_b}, "
                             f"not n={N}")


def mse_gap(mse, ref):
    return abs(mse - ref) / ref


def predict_phase(km, X_held):
    """Predict through a published snapshot; agreement with float64."""
    from repro.serve import CodebookSnapshot
    snap = CodebookSnapshot.create(1, km.export_codebook())
    agree = total = 0
    for rows in REQUEST_ROWS:
        Xq = X_held[:rows]
        a, dist = snap.predict_with_distance(Xq)
        if a.shape != (rows,) or a.min() < 0 or a.max() >= K:
            raise AssertionError(f"predict({rows}) labels out of range")
        if not np.all(np.isfinite(dist)):
            raise AssertionError(f"predict({rows}) gave a non-finite value")
        agree += int((a == nearest_f64(Xq, snap.centroids)[0]).sum())
        total += rows
    return agree / total


def log(msg):
    print(msg, flush=True)


def one_chip(seed: int, clog: CompileLog):
    import jax

    X, X_held = make_data(seed)
    log(f"data: X {X.shape} f32 ({X.nbytes / 1e9:.2f} GB), held-out "
        f"{X_held.shape} (seed {seed} / {seed + 1})")
    cfg = base_config(seed)

    snap = clog.snapshot()
    km, cold_s = fit(X, X_held, cfg)
    cold = clog.since(snap)
    log(f"plan: {json.dumps(km.outcome_.kernel_plan, sort_keys=True)}")
    check_fit(km, "pallas")
    log(f"set-up (first fit, compiles included): {cold_s:.2f} s; "
        f"backend compile {cold['compile_s']:.2f} s, persistent cache "
        f"hits {cold['cache_hits']}, writes {cold['cache_writes']}")

    snap = clog.snapshot()
    km, warm_s = fit(X, X_held, cfg)
    s = fit_summary(km, warm_s)
    log(f"pallas fit: rounds {s['rounds']}, final b {s['final_b']}, "
        f"converged {s['converged']}, wall {warm_s:.3f} s "
        f"(compile inside: {clog.since(snap)['compile_s']:.3f} s)")
    mse_p = held_mse(km.cluster_centers_, X_held)

    with jax.default_matmul_precision("highest"):
        ref_km, ref_s = fit(X, X_held, dataclasses.replace(
            cfg, kernel_backend="ref"))
    rs = fit_summary(ref_km, ref_s)
    if rs["final_b"] != N:
        raise AssertionError(f"ref fit stopped at b={rs['final_b']}")
    mse_r = held_mse(ref_km.cluster_centers_, X_held)
    gap = mse_gap(mse_p, mse_r)
    log(f"ref fit (highest precision): rounds {rs['rounds']}, final b "
        f"{rs['final_b']}, wall {ref_s:.2f} s (compiles included)")
    log(f"held-out MSE (float64): pallas {mse_p:.6f}, ref {mse_r:.6f}, "
        f"gap {gap:.4%} (limit {MSE_RTOL:.0%})")
    if gap > MSE_RTOL:
        raise AssertionError(f"MSE gap {gap:.4%} over {MSE_RTOL:.0%}")

    agreement = predict_phase(km, X_held)
    log(f"predict {REQUEST_ROWS} rows: label agreement with float64 "
        f"{agreement:.4%} (limit {MIN_AGREEMENT:.0%})")
    if agreement < MIN_AGREEMENT:
        raise AssertionError(f"label agreement {agreement:.4%}")


def spread_devices(arr, name):
    """The distinct devices holding ``arr``; fails unless there are 4."""
    devs = {shard.device for shard in arr.addressable_shards}
    if len(devs) != 4:
        raise AssertionError(f"{name} sits on {len(devs)} devices, not 4")
    return sorted(d.id for d in devs)


def four_chips(seed: int):
    import jax

    if len(jax.devices()) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX has "
                             f"{len(jax.devices())}")
    X, X_held = make_data(seed)
    cfg = base_config(seed)
    local, local_s = fit(X, X_held, cfg)
    check_fit(local, "local")
    mse_l = held_mse(local.cluster_centers_, X_held)
    log(f"local fit (1 chip): {fit_summary(local, local_s)}, held-out "
        f"MSE {mse_l:.6f}")
    runs = {
        "mesh": (dataclasses.replace(cfg, backend="mesh",
                                     data_axes=("data",)),
                 jax.make_mesh((4,), ("data",))),
        "xl": (dataclasses.replace(cfg, backend="xl", data_axes=("data",),
                                   model_axis="model"),
               jax.make_mesh((2, 2), ("data", "model"))),
    }
    for name, (c, mesh) in runs.items():
        km, wall = fit(X, X_held, c, mesh=mesh)
        check_fit(km, name)
        state = km.outcome_.state
        rows_on = spread_devices(state.points.a, f"{name} row state")
        cents_on = spread_devices(state.stats.C, f"{name} centroids")
        mse = held_mse(km.cluster_centers_, X_held)
        gap = mse_gap(mse, mse_l)
        agree = float(np.mean(km.labels_ == local.labels_))
        log(f"{name} fit: {fit_summary(km, wall)}; rows on devices "
            f"{rows_on}, centroids on {cents_on}; held-out MSE {mse:.6f}, "
            f"gap to local {gap:.4%}; label agreement with local "
            f"{agree:.4%}")
        if gap > MSE_RTOL:
            raise AssertionError(f"{name} MSE gap {gap:.4%} over "
                                 f"{MSE_RTOL:.0%}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro.util.env import enable_compile_cache
    log(f"device_kind: {dev.device_kind}; devices: {len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache: {enable_compile_cache()}")
    clog = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed, clog)
    else:
        four_chips(args.seed)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}; total "
        f"{time.perf_counter() - t0:.1f} s; backend compile "
        f"{clog.compile_s:.1f} s, cache hits {clog.hits}, writes "
        f"{clog.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
