"""`LocalEngine` — single-process bucketed-jit rounds."""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import FitConfig
from repro.api.engines.base import EngineRun
from repro.core import rounds
from repro.core.state import (ElkanBounds, KMeansState, PointState,
                              full_mse, init_state)
from repro.kernels.plan import resolve_plan
from repro.util.device import piece_update

# shared with estimator.partial_fit so streaming batches of a repeated
# shape hit the same jit cache as fit(). The resolved KernelPlan is a
# frozen (hashable) dataclass, so it rides the static args exactly like
# the bucket keys — one trace per (b, capacity, plan) tuple, and the
# plan is constant for a fit.
nested_jit = jax.jit(
    rounds.nested_round,
    static_argnames=("b", "rho", "bounds", "capacity", "use_shalf",
                     "plan", "data_axes"))
_mb_jit = jax.jit(rounds.mb_round, static_argnames=("fixed", "plan"))
_lloyd_jit = jax.jit(rounds.lloyd_round, static_argnames=("plan",))


# rows fetched off a ChunkStore per device-buffer update: bounds the
# host memory in flight and keeps the update-jit cache at one executable
# for full segments plus a handful of ragged tails
_IO_SEG_ROWS = 65536

# the in-memory fit's shuffle: storage row i <- X[perm[i]], gathered on
# the device from the rows as the caller gave them. One executable per
# (N, d); a copy of f32 rows, so bit-equal to the host's X[perm].
# (`jnp.take` has no in-bounds mode; this is its gather with one)
_take_rows = jax.jit(lambda X, perm: X.at[perm].get(
    mode="promise_in_bounds", unique_indices=True))


def gather_path(need_bytes: int, stats: Optional[Mapping[str, int]]) -> str:
    """Where an in-memory fit shuffles its rows: ``"device"`` when the
    gather's ``need_bytes`` fit in what the device has free by its
    ``memory_stats()`` ``stats``, ``"host"`` otherwise. A backend that
    reports no statistics (the CPU) gathers on the device."""
    if not stats or "bytes_limit" not in stats:
        return "device"
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    return "device" if need_bytes <= free else "host"


def _memory_stats() -> Optional[Mapping[str, int]]:
    """The default device's ``memory_stats()`` (None on the CPU)."""
    return jax.devices()[0].memory_stats()


def _gather_bytes(X: np.ndarray, perm: np.ndarray) -> int:
    """Device bytes `_take_rows` holds at its peak on these shapes: the
    unshuffled rows, the shuffled rows, and its temporaries (on a TPU,
    the layout copies around the gather: more than the rows again).
    Compiles on the first fit of a shape and reads the jit's cache
    after."""
    m = _take_rows.lower(X, perm).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


class _LocalRun(EngineRun):
    def __init__(self, X, config: FitConfig, X_val, init_C, obs=None):
        from repro.data.store import (ChunkStore, dataset_fingerprint,
                                      store_permutation)
        self.bind_obs(obs)
        rng = np.random.default_rng(config.seed)
        self._store = X if isinstance(X, ChunkStore) else None
        with self._obs.span("fit.shuffle"):
            if self._store is not None:
                # out-of-core: the chunk-blocked permutation keeps the
                # disk frontier sequential — see repro.data.store.source
                N = self._store.n
                perm = store_permutation(N, self._store.chunk_rows,
                                         config.seed,
                                         shuffle=config.shuffle)
            else:
                X = np.asarray(X)
                N = X.shape[0]
                perm = (rng.permutation(N) if config.shuffle
                        else np.arange(N))
        # where the in-memory shuffle gathers its rows (None: no gather)
        gather = None
        if self._store is None and config.shuffle:
            perm32 = perm.astype(np.int32)
            stats = _memory_stats()
            gather = gather_path(
                _gather_bytes(X, perm32) if stats else 0, stats)
        with self._obs.span("fit.to_device", gather=gather):
            if self._store is not None:
                # a zero device buffer filled lazily to the current
                # nested prefix (`_ensure_prefix`); the host never holds
                # more than one fetch segment of rows at a time. The
                # shared donated segment writer (repro.util.device)
                # fills it: the donation auditor proves it aliases
                # rather than copies
                self._Xd = jnp.zeros((N, self._store.d), jnp.float32)
                self._filled = 0
                self._upd = piece_update
            else:
                if gather == "device":
                    # the rows go over as they are; the put of the
                    # unshuffled buffer is dropped once the gather that
                    # reads it is dispatched
                    self._Xd = _take_rows(jnp.asarray(X),
                                          jnp.asarray(perm32))
                else:
                    # "host": too little device memory for the gather
                    self._Xd = jnp.asarray(X if gather is None
                                           else X[perm])
                self._filled = N
            self._Xv = jnp.asarray(X_val) if X_val is not None else None
        self._config = config
        self._rng = rng
        self._perm = perm
        with self._obs.span("fit.init"):
            if self._store is not None:
                self.data_fingerprint = self._store.fingerprint()
                # paper init needs the first k shuffled rows materialised
                self._ensure_prefix(min(N, max(config.k, 1)))
            else:
                self.data_fingerprint = dataset_fingerprint(X)
            state = init_state(self._Xd, config.k, bounds=config.bounds)
            if init_C is not None:       # warm start (checkpoint restart)
                state = dataclasses.replace(
                    state, stats=dataclasses.replace(
                        state.stats, C=jnp.asarray(init_C, jnp.float32)))
            # kernel dispatch: resolved ONCE for the fit at its maximum
            # batch bucket; every round below threads this plan
            self.kernel_plan = resolve_plan(
                config.kernel_backend, b=N, k=config.k,
                d=self._Xd.shape[1], bounds=config.bounds)
        self.state = state
        self.b = min(config.b0, N)
        self.b_max = N
        self.n_shards = 1
        self.n_active_target = N
        self.orig_index = perm        # storage row i holds X[perm[i]]
        self.n_points = N
        # mb/mbf resampling stream (paper footnote 1: cycle a reshuffle)
        self._mb_pos = 0
        self._mb_perm = rng.permutation(N)

    def _ensure_prefix(self, b: int) -> None:
        """Fill the device buffer with shuffled rows [filled, b) off the
        store, in bounded segments. No-op for in-memory fits and for
        already-covered prefixes — steady-state rounds fetch nothing."""
        if self._store is None or b <= self._filled:
            return
        with self._obs.span("ingest", rows=b - self._filled):
            lo = self._filled
            while lo < b:
                hi = min(b, lo + _IO_SEG_ROWS)
                rows = self._store.take(self._perm[lo:hi]).astype(
                    np.float32, copy=False)
                self._Xd = self._upd(self._Xd, jnp.asarray(rows),
                                     np.int32(lo))
                lo = hi
            self._filled = b

    def store_metrics(self):
        if self._store is None:
            return None
        return self._store.metrics.to_dict()

    def nested_step(self, state, b, capacity):
        self._ensure_prefix(b)
        return nested_jit(self._Xd, state, b=b, rho=self._config.rho,
                          bounds=self._config.bounds, capacity=capacity,
                          use_shalf=self._config.use_shalf,
                          plan=self.kernel_plan)

    def lloyd_step(self, state):
        return _lloyd_jit(self._Xd, state, plan=self.kernel_plan)

    def mb_step(self, state, fixed):
        N, b = self.b_max, self.b
        if self._mb_pos + b > N:
            self._mb_perm = self._rng.permutation(N)
            self._mb_pos = 0
        idx = jnp.asarray(self._mb_perm[self._mb_pos:self._mb_pos + b])
        self._mb_pos += b
        return _mb_jit(self._Xd, idx, state, fixed=fixed,
                       plan=self.kernel_plan)

    def eval_mse(self, state):
        if self._Xv is None:
            return None
        return float(full_mse(self._Xv, state.stats.C))

    # -- checkpointing ------------------------------------------------------
    # storage row i holds shuffle position i, so storage order IS the
    # canonical order for the local engine.

    def capture(self, state):
        tree = {
            "stats": jax.tree.map(np.asarray, state.stats),
            "a": np.asarray(state.points.a),
            "d": np.asarray(state.points.d),
            "lb": np.asarray(state.points.lb),
            "round": np.asarray(state.round),
            "mb_perm": np.asarray(self._mb_perm),
        }
        if state.elkan is not None:
            tree["elkan_l"] = np.asarray(state.elkan.l)
        meta = {
            "engine": "local", "n_shards": 1, "n_points": self.n_points,
            "has_mb": True, "has_elkan": state.elkan is not None,
            "mb_pos": self._mb_pos,
            "rng_state": self._rng.bit_generator.state,
        }
        return tree, meta

    def restore(self, store, step, meta):
        proto = {"stats": self.state.stats,
                 "a": self.state.points.a, "d": self.state.points.d,
                 "lb": self.state.points.lb, "round": self.state.round}
        if meta.get("has_elkan"):
            if self.state.elkan is None:
                raise ValueError(
                    "checkpoint carries elkan bounds but this config "
                    "does not use bounds='elkan'")
            proto["elkan_l"] = self.state.elkan.l
        if meta.get("has_mb"):
            proto["mb_perm"] = jnp.asarray(self._mb_perm)
        got = store.restore(proto, step=step)
        if meta.get("has_mb"):
            self._mb_perm = np.asarray(got["mb_perm"])
            self._mb_pos = int(meta["mb_pos"])
        if meta.get("rng_state") is not None:
            self._rng.bit_generator.state = meta["rng_state"]
        points = PointState(a=got["a"], d=got["d"], lb=got["lb"])
        elkan = (ElkanBounds(l=got["elkan_l"]) if meta.get("has_elkan")
                 else None)
        return KMeansState(stats=got["stats"], points=points,
                           elkan=elkan, round=got["round"])


class LocalEngine:
    """Single-process engine over the bucketed-jit round functions."""

    def begin(self, X, config: FitConfig, *, X_val=None,
              init_C=None, obs=None) -> EngineRun:
        return _LocalRun(X, config, X_val, init_C, obs)
