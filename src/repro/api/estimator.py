"""`NestedKMeans`: the sklearn-style front door to every engine.

    from repro.api import FitConfig, NestedKMeans

    km = NestedKMeans(FitConfig(k=50, algorithm="tb", b0=2000))
    km.fit(X_train, X_val=X_val)
    labels = km.predict(X_new)

`partial_fit` is the serving-path primitive: it folds a fresh batch into
the running S/v statistics with ONE nested round (new points enter with
``a == -1`` exactly like a batch doubling), so a stream of batches keeps
refining the codebook without re-touching old data.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import FitConfig
from repro.api.engines import Engine, make_engine, nested_jit
from repro.api.engines.base import as_sink
from repro.api.loop import FitOutcome, fetch_round_info, run_loop
from repro.api.telemetry import RoundCallback, Telemetry
from repro.checkpoint.store import CheckpointStore
from repro.core.state import full_mse, init_state
from repro.kernels import ops

# config fields that must agree between a checkpoint manifest and the
# resuming config for the restored state to be meaningful (max_rounds /
# budgets / backend / shard layout may all change across a restart)
_RESUME_KEYS = ("k", "algorithm", "rho", "b0", "bounds", "seed",
                "use_shalf", "shuffle")


class NotFittedError(RuntimeError):
    pass


class NestedKMeans:
    """Estimator over a `FitConfig` and an execution `Engine`.

    After `fit` / `partial_fit`:
      cluster_centers_   (k, d) float32 ndarray
      labels_            (n,) assignments of the fitted data (fit only)
      inertia_           batch MSE at the last round (fit only)
      telemetry_         List[Telemetry], one per host round
      converged_         bool
      n_rounds_          len(telemetry_)

    Thread-safety: `fit` / `partial_fit` / `adopt` serialise on an
    internal lock, so a background refresher may stream batches while
    other threads call `predict` / `transform` — the readers never take
    the lock (they read `_stats` once; the whole stats pytree is swapped
    atomically, never mutated in place). `export_codebook` snapshots the
    codebook under the same lock for `repro.serve`.
    """

    def __init__(self, config: FitConfig, *, engine: Optional[Engine] = None,
                 mesh=None, on_round: Optional[RoundCallback] = None):
        self.config = config
        self.engine = engine or make_engine(config, mesh=mesh)
        self.on_round = on_round
        self.telemetry_: List[Telemetry] = []
        self._outcome: Optional[FitOutcome] = None
        self._stats = None          # streaming ClusterStats (partial_fit)
        self._outcome_stale = False  # partial_fit moved the centroids
        # serialises the WRITERS (fit/partial_fit/adopt); readers are
        # lock-free — they load self._stats once and work on that pytree
        self._lock = threading.RLock()

    # -- fitting ------------------------------------------------------------

    def fit(self, X=None, *, X_val=None,
            init_C: Optional[np.ndarray] = None,
            resume: bool = False) -> "NestedKMeans":
        """Run the configured algorithm to convergence / budget.

        ``X`` may be an in-memory array, an on-disk chunk-store path (or
        open `ChunkStore`) for an out-of-core fit, or omitted entirely
        when ``config.data_source`` names the store. Store-backed fits
        stream the nested prefix from disk and are bit-identical to the
        in-memory fit over the same row sequence (nested family only —
        mb/lloyd rescan the full dataset every round).

        ``resume=True`` (requires ``config.checkpoint``) restores the
        latest in-loop checkpoint from ``checkpoint_dir`` and continues
        the fit from there — bit-identically on the same engine, and
        elastically across a shard-count (or local<->mesh) change. With
        no checkpoint on disk yet the fit simply starts fresh. Resuming
        against a different dataset than the checkpoint's is a loud
        error (the manifest carries a dataset fingerprint).
        """
        from pathlib import Path
        from repro.data.store import ChunkStore
        with self._lock:
            if X is None:
                if self.config.data_source is None:
                    raise ValueError(
                        "fit() needs data: pass X (array or store "
                        "path), or set config.data_source")
                X = self.config.data_source
            if isinstance(X, (str, Path)):
                X = ChunkStore(X)
            if isinstance(X, ChunkStore):
                n = X.n
            else:
                n = int(np.asarray(X).shape[0])
            cfg = self.config.resolve(n)
            if isinstance(X, ChunkStore) and cfg.algorithm not in (
                    "tb", "gb"):
                raise ValueError(
                    f"out-of-core fits stream the nested prefix; "
                    f"algorithm={self.config.algorithm!r} needs the "
                    f"full dataset in memory every round (pass X as an "
                    f"array)")
            if resume and cfg.checkpoint is None:
                raise ValueError(
                    "fit(resume=True) requires config.checkpoint")
            observer = None
            if cfg.trace_dir is not None:
                # built lazily so untraced fits never import repro.obs;
                # process_id keys the per-process JSONL files on
                # multihost (every process traces its own host loop)
                from repro.obs import FitObserver
                observer = FitObserver(
                    cfg.trace_dir, process_id=jax.process_index(),
                    k=cfg.k,
                    d=int(X.d if isinstance(X, ChunkStore)
                          else np.shape(X)[-1]),
                    device_kind=jax.devices()[0].device_kind,
                    meta={"backend": cfg.backend,
                          "algorithm": cfg.algorithm,
                          "bounds": cfg.bounds,
                          "n_points": n, "seed": cfg.seed})
            obs = as_sink(observer)
            try:
                with obs.span("fit.begin"):
                    run = self.engine.begin(X, cfg, X_val=X_val,
                                            init_C=init_C, obs=obs)
                resume_from, resolved = (self._resume_point(run, cfg)
                                         if resume else (None, None))
                out = run_loop(run, cfg, on_round=self.on_round,
                               resume_from=resume_from,
                               resolved_resume=resolved, obs=obs)
            finally:
                obs.close()
            self._outcome = out
            # fetch_stats: the state's own leaves on single-process
            # engines; a host gather on multihost (so predict/export
            # never touch non-addressable shards)
            self._stats = run.fetch_stats(out.state)
            self._outcome_stale = False
            # copy: later partial_fit records must not mutate the
            # outcome's own telemetry history
            self.telemetry_ = list(out.telemetry)
            return self

    @staticmethod
    def _resume_point(run, cfg: FitConfig):
        """(store, (step, extra)) of the checkpoint to resume from, or
        (None, None) when ``checkpoint_dir`` holds none yet."""
        store = CheckpointStore(cfg.checkpoint.checkpoint_dir,
                                keep=cfg.checkpoint.keep)
        # the resume decision goes through the run so it is
        # process-replicated: on multihost the coordinator's filesystem
        # is the source of truth and its verdict is broadcast — no
        # process can start fresh while another restores
        step, extra = run.resolve_resume(store)
        if step is None:
            return None, None
        saved = (extra or {}).get("config")
        if saved:
            want = cfg.to_dict()
            bad = [k for k in _RESUME_KEYS
                   if k in saved and saved[k] != want[k]]
            if bad:
                raise ValueError(
                    f"checkpoint manifest disagrees with the resuming "
                    f"config on {bad}; refusing to restore a foreign fit")
        return store, (step, extra)

    def partial_fit(self, X) -> "NestedKMeans":
        """Fold one streaming batch into the codebook (one nested round).

        The incoming points enter unseen (``a == -1``): the round assigns
        them, adds them to S/v, and moves the centroids to the updated
        means — the exact update a batch doubling applies to new points
        inside `fit`. Repeated calls keep absorbing traffic at O(batch)
        cost per call.

        Runs on ANY backend: the local engine streams through one jitted
        round; the sharded engines (mesh/xl/multihost) place the batch
        with their usual layout and run one full-prefix sharded round,
        carrying the running statistics in via `EngineRun.place_stats`.
        Each distinct batch shape compiles one executable per backend —
        stream fixed-size micro-batches (as `repro.serve.ClusterService`
        does) to stay on one.
        """
        with self._lock:
            X = np.asarray(X)
            cfg = self.config.resolve(int(X.shape[0]))
            if self._stats is None and X.shape[0] < cfg.k:
                raise ValueError(
                    f"first partial_fit batch must have >= k={cfg.k} "
                    f"rows (repro.serve.IngestQueue accumulates sub-k "
                    f"contributions into a big-enough first batch)")
            t_prev = self.telemetry_[-1].t if self.telemetry_ else 0.0
            t0 = time.perf_counter()
            if cfg.backend == "local":
                Xd = jnp.asarray(X)
                state = init_state(Xd, cfg.k, bounds=cfg.bounds)
                if self._stats is not None:
                    # carry the running statistics; bounds state restarts
                    # per batch (new points have no history to bound
                    # against)
                    state = dataclasses.replace(
                        state, stats=jax.tree.map(jnp.asarray,
                                                  self._stats))
                from repro.kernels.plan import resolve_plan
                plan = resolve_plan(cfg.kernel_backend,
                                    b=int(X.shape[0]), k=cfg.k,
                                    d=int(X.shape[1]), bounds=cfg.bounds)
                new_state, info = nested_jit(
                    Xd, state, b=int(X.shape[0]), rho=cfg.rho,
                    bounds=cfg.bounds, capacity=None,
                    use_shalf=cfg.use_shalf, plan=plan)
                jax.block_until_ready(new_state.stats.C)
                new_stats = new_state.stats
            else:
                # sharded streaming: place the batch like a fit would
                # (shuffle + interleave + structural pads are harmless —
                # the S/v delta is order-independent and pads are masked
                # out by n_valid), then run ONE full-prefix round
                run = self.engine.begin(
                    X, cfg, init_C=(np.asarray(self._stats.C)
                                    if self._stats is not None else None))
                state = run.state
                if self._stats is not None:
                    state = run.place_stats(state, self._stats)
                new_state, info = run.nested_step(state, run.b_max, None)
                jax.block_until_ready(new_state.stats.C)
                new_stats = run.fetch_stats(new_state)
            self._stats = new_stats
            if self._outcome is not None:
                # the centroids have moved past the fit's outcome: its
                # labels/state no longer describe this estimator
                self._outcome_stale = True
            # one transfer + the shared record builder — the same path
            # run_loop takes, so the two telemetry streams cannot drift
            hinfo = fetch_round_info(info)
            rec = Telemetry.from_round(
                hinfo, round=len(self.telemetry_),
                t=t_prev + time.perf_counter() - t0)
            self.telemetry_.append(rec)
            if self.on_round:
                self.on_round(rec)
            return self

    def adopt(self, outcome: FitOutcome) -> "NestedKMeans":
        """Rehydrate this estimator from a previously produced outcome.

        Lets a serving process rebuild an estimator from a `FitOutcome`
        computed elsewhere (e.g. by `repro.api.fit` in a training job)
        and keep streaming into it with `partial_fit`.
        """
        if outcome.config.k != self.config.k:
            raise ValueError(
                f"cannot adopt an outcome fitted with "
                f"k={outcome.config.k} into an estimator configured "
                f"for k={self.config.k}")
        with self._lock:
            self._outcome = outcome
            self._stats = outcome.state.stats
            self._outcome_stale = False
            self.telemetry_ = list(outcome.telemetry)
            return self

    def export_codebook(self) -> Dict[str, Any]:
        """Atomic host-side copy of the codebook, for snapshot publishers.

        Returns ``{"centroids", "counts", "n_rounds", "batch_mse"}``
        captured under the writer lock, so a concurrent `partial_fit`
        can never be observed half-applied. The arrays are fresh numpy
        copies owned by the caller.
        """
        with self._lock:
            self._require_fitted()
            return {
                "centroids": np.array(self._stats.C, dtype=np.float32,
                                      copy=True),
                "counts": np.array(self._stats.v, dtype=np.float32,
                                   copy=True),
                "n_rounds": len(self.telemetry_),
                "batch_mse": self.inertia_,
            }

    # -- fitted attributes --------------------------------------------------

    def _require_fitted(self):
        if self._stats is None:
            raise NotFittedError("call fit() or partial_fit() first")

    @property
    def cluster_centers_(self) -> np.ndarray:
        self._require_fitted()
        return np.asarray(self._stats.C)

    @property
    def counts_(self) -> np.ndarray:
        """Per-cluster membership counts v (codebook occupancy)."""
        self._require_fitted()
        return np.asarray(self._stats.v)

    @property
    def stats_(self):
        """The running `ClusterStats` (C/S/v/sse/p) — host-reachable on
        every backend (fit/partial_fit store them through the engine's
        `fetch_stats`, so even a multi-process fit's stats can be read,
        adopted or re-placed from any one process)."""
        self._require_fitted()
        return self._stats

    def _require_fresh_outcome(self, what: str):
        if self._outcome is None:
            raise NotFittedError(f"{what} requires a full fit()")
        if self._outcome_stale:
            raise NotFittedError(
                f"{what} is stale: partial_fit() has moved the centroids "
                f"since fit(); use predict(X) for fresh assignments or "
                f"refit")

    @property
    def labels_(self) -> np.ndarray:
        """Assignments of the fitted data, in the caller's row order
        (-1 = row never entered the nested batch). Raises
        `NotFittedError` once `partial_fit` has moved the centroids past
        the fit that produced them."""
        self._require_fitted()
        self._require_fresh_outcome("labels_")
        return self._outcome.labels

    @property
    def inertia_(self) -> float:
        self._require_fitted()
        for rec in reversed(self.telemetry_):
            if rec.batch_mse is not None:
                return rec.batch_mse
        return float("nan")

    @property
    def converged_(self) -> bool:
        return self._outcome.converged if self._outcome else False

    @property
    def n_rounds_(self) -> int:
        return len(self.telemetry_)

    @property
    def outcome_(self) -> FitOutcome:
        """The `FitOutcome` of the last fit(). Raises `NotFittedError`
        once `partial_fit` has moved the centroids past it."""
        self._require_fitted()
        self._require_fresh_outcome("outcome_")
        return self._outcome

    @property
    def final_mse_(self) -> float:
        from repro.api.telemetry import final_val_mse
        return final_val_mse(self.telemetry_)

    # -- inference ----------------------------------------------------------

    def predict(self, X) -> np.ndarray:
        """Nearest-centroid index for each row of ``X``."""
        self._require_fitted()
        a, _, _ = ops.assign_top2(jnp.asarray(X), self._stats.C,
                                  backend=self.config.kernel_backend)
        return np.asarray(a)

    def transform(self, X) -> np.ndarray:
        """Euclidean distance of each row to every centroid: (n, k)."""
        self._require_fitted()
        from repro.kernels import ref
        d2 = ref.pairwise_dist2(jnp.asarray(X), self._stats.C)
        return np.asarray(jnp.sqrt(jnp.maximum(d2, 0.0)))

    def score(self, X) -> float:
        """Negative inertia (−sum of squared distances), sklearn-style."""
        self._require_fitted()
        X = jnp.asarray(X)
        return -float(full_mse(X, self._stats.C)) * int(X.shape[0])
