"""Quickstart: nested mini-batch k-means (tb-inf) vs Lloyd.

    PYTHONPATH=src python examples/quickstart.py

Clusters a synthetic infinite-MNIST-style dataset with the paper's
turbocharged algorithm through the unified `repro.api` surface and
prints the MSE-vs-work trajectory. On this CPU container it runs a
scaled-down N; the identical config drives the multi-pod mesh engine
(see examples/kmeans_e2e.py) by flipping `backend="mesh"`.
"""
import dataclasses

import numpy as np

from repro.api import FitConfig, NestedKMeans
from repro.data.synthetic import infmnist_like
from repro.util.env import enable_compile_cache

enable_compile_cache()
N, K = 20_000, 50
X = infmnist_like(N + 2000, seed=0)
X_train, X_val = X[:N], X[N:]

print(f"clustering N={N} d={X.shape[1]} k={K}")
cfg = FitConfig(k=K, algorithm="tb", rho=float("inf"), b0=2000,
                bounds="hamerly2", max_rounds=400, time_budget_s=30,
                eval_every=5, seed=0)
km = NestedKMeans(cfg).fit(X_train, X_val=X_val)
print(f"\ntb-inf: {km.n_rounds_} rounds, converged={km.converged_}, "
      f"final MSE={km.final_mse_:.5f}")
print("round |      b | recomputed | batch MSE")
for t in km.telemetry_[::5]:
    if t.batch_mse is None:
        continue
    print(f"{t.round:5d} | {t.b:6d} | {t.n_recomputed:10d} | "
          f"{t.batch_mse:.5f}")

ll = NestedKMeans(dataclasses.replace(
    cfg, algorithm="lloyd", max_rounds=100, time_budget_s=float("inf"),
    eval_every=10 ** 9)).fit(X_train, X_val=X_val)
print(f"\nlloyd: {ll.n_rounds_} rounds, final MSE={ll.final_mse_:.5f}")
print(f"tb-inf work saved: last-round distance computations "
      f"{km.telemetry_[-2].n_recomputed} / {N}")
