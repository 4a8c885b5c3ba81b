"""End-to-end production driver for the paper's technique.

Demonstrates the full substrate on one box, entirely through the
unified `repro.api` surface:
  * sharded data pipeline (nested-prefix property across shards),
  * the same FitConfig driving the LocalEngine or the MeshEngine
    (shard_map; run with
    XLA_FLAGS=--xla_force_host_platform_device_count=8 for 8 shards),
  * IN-LOOP checkpointing + kill-and-resume: `run_loop` saves the full
    host-schedule state (S/v statistics, batch-growth position,
    patience, work clock, telemetry) every N rounds, so the resumed fit
    is bit-identical to an uninterrupted one — not a warm start that
    discards the nested statistics,
  * validation MSE telemetry.

    PYTHONPATH=src python examples/kmeans_e2e.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/kmeans_e2e.py --distributed
"""
import argparse
import dataclasses
import tempfile

import jax
import jax.numpy as jnp

from repro.api import CheckpointConfig, FitConfig, NestedKMeans
from repro.core.state import full_mse
from repro.data.synthetic import infmnist_like
from repro.util.env import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--n", type=int, default=20_000)
    args = ap.parse_args()
    enable_compile_cache()

    X = infmnist_like(args.n + 2000, seed=0)
    X_train, X_val = X[: args.n], X[args.n:]
    k = 50

    if args.distributed:
        ndev = len(jax.devices())
        mesh = jax.make_mesh((ndev, 1), ("data", "model"))
        cfg = FitConfig(k=k, algorithm="tb", b0=2048, rho=float("inf"),
                        bounds="hamerly2", max_rounds=300, seed=0,
                        backend="mesh", data_axes=("data",),
                        capacity_floor=256)
        km = NestedKMeans(cfg, mesh=mesh).fit(X_train)
        print(f"distributed over {ndev} devices: "
              f"rounds={km.n_rounds_} converged={km.converged_}")
        mse = float(full_mse(jnp.asarray(X_val),
                             jnp.asarray(km.cluster_centers_)))
        print(f"val MSE {mse:.5f}")
        return

    # single-host run with in-loop checkpointing + kill-and-resume
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointConfig(checkpoint_dir=d, save_every=4, keep=2)
        cfg = FitConfig(k=k, algorithm="tb", b0=2048, bounds="hamerly2",
                        max_rounds=200, eval_every=10, seed=0,
                        checkpoint=ck)

        # phase 1: the fit "crashes" after 12 rounds. Every save_every
        # rounds run_loop wrote the FULL loop state — KMeansState (S/v,
        # bounds), current b, capacity bucket, patience, work clock,
        # telemetry — alongside the FitConfig.to_dict() manifest.
        km1 = NestedKMeans(dataclasses.replace(cfg, max_rounds=12))
        km1.fit(X_train)
        print(f"phase-1: {km1.n_rounds_} rounds, then 'crash'; "
              f"checkpointed b={km1.telemetry_[-1].b}")

        # phase 2: resume. The restored fit continues the growth
        # schedule bit-identically to an uninterrupted run (same
        # centroids, same telemetry) — and the restore is elastic: the
        # same checkpoint also resumes on a mesh at any shard count.
        km2 = NestedKMeans(cfg)
        km2.fit(X_train, X_val=X_val, resume=True)
        print(f"phase-2 (resumed at round {km1.n_rounds_}): "
              f"converged={km2.converged_} after {km2.n_rounds_} total "
              f"rounds, final MSE={km2.final_mse_:.5f}")


if __name__ == "__main__":
    main()
