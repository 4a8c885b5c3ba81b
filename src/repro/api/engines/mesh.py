"""`MeshEngine` — shard_map over a (single-process) device mesh.

`_MeshRun` is also the base class of the XL and multihost runs: all
placement goes through two hooks — `_put_global(arr, spec)` (host/local
array -> mesh-placed global array) and `_fetch(arr)` (global array ->
host numpy) — and the layout itself is a PartitionSpec pytree from
`_state_specs`. A subclass that changes WHERE things live (k-sharded
stats, process-spanning shards) overrides those hooks; the data layout
math, the canonical checkpoint order and the round schedule are
inherited untouched.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.config import FitConfig
from repro.api.engines.base import EngineRun
from repro.core.state import (ClusterStats, ElkanBounds, KMeansState,
                              PointState, full_mse)


def _slice_shape(idx, shape):
    """Concrete shape of a device's slice of a global array."""
    return tuple((sl.stop if sl.stop is not None else dim)
                 - (sl.start or 0) for sl, dim in zip(idx, shape))


# donated per-device-piece writer for `_ensure_prefix` — shared with
# the local engine and proven aliased by the donation auditor; see
# repro.util.device for why it is NOT a shard_map'd update.
from repro.util.device import piece_update as _piece_update


class _MeshRun(EngineRun):
    _engine_name = "mesh"

    def __init__(self, X, config: FitConfig, mesh, X_val, init_C):
        from repro.data.pipeline import nested_shard_layout
        from repro.data.store import (ChunkStore, StoredShardSource,
                                      dataset_fingerprint)

        data_axes = config.data_axes
        n_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
        self._config = config
        self._mesh = mesh
        if isinstance(X, ChunkStore):
            # out-of-core: the layout's shuffle is the store's chunk-
            # blocked permutation (sequential disk frontier); rows are
            # fetched lazily up to the nested prefix (`_ensure_prefix`)
            # instead of placed up front.
            self._src = StoredShardSource(X, n_shards, seed=config.seed,
                                          shuffle=config.shuffle)
            N_real = X.n
            self._dim = X.d
            lay = self._src.layout
            self.data_fingerprint = X.fingerprint()
        else:
            # the placement (shuffle + structural tail pads + round-robin
            # interleave) is shared with data.pipeline.KMeansShardedSource;
            # padded rows sit at the tail of every shard and b_local is
            # capped below them, so they can never enter a nested prefix.
            self._src = None
            X = np.asarray(X)
            N_real = X.shape[0]
            self._dim = X.shape[1]
            lay = nested_shard_layout(N_real, n_shards, seed=config.seed,
                                      shuffle=config.shuffle)
            self.data_fingerprint = dataset_fingerprint(X)
        self._layout = lay
        N = lay.n_storage
        self._N = N
        self.n_shards = n_shards
        self.n_points = N_real
        self.n_active_target = N_real
        self.b = max(1, min(config.b0, N_real) // n_shards)
        # every shard's real rows are prefix-contiguous in its storage
        # slice; shards whose last storage row is a structural pad cap
        # their active prefix via the per-shard n_valid mask inside the
        # round, so b_max covers EVERY real row — including the tail
        # rows of the low shards when N_real % n_shards != 0.
        self.b_max = max(1, N // n_shards)
        # per-shard real-row cap is derived inside the sharded round
        # from the shard's axis index; None disables masking entirely
        self._n_real = N_real if N_real % n_shards else None
        # storage row shard*(N/s)+i holds shuffle position i*s+shard;
        # positions >= N_real are structural pads
        self._pos = lay.pos
        self.orig_index = lay.orig_index()
        self._Xv = jnp.asarray(X_val) if X_val is not None else None

        if self._src is None:
            self._Xd = self._place_data(X)
            self._filled = self.b_max
        else:
            self._Xd = self._zeros_data()
            self._filled = 0
        if init_C is not None:
            C0 = np.asarray(init_C, np.float32)
        else:
            # paper init: first k of the global shuffle. Indices past
            # N_real (k > N_real only) are structural pads == X[0].
            idx = lay.perm[:config.k]
            idx = np.where(idx < N_real, idx, 0)
            C0 = (self._src.store.take(idx) if self._src is not None
                  else X[idx]).astype(np.float32)
        # kernel dispatch: one plan for the fit, resolved at the
        # per-shard batch bucket (the shapes the kernels actually see)
        from repro.kernels.plan import resolve_plan
        self.kernel_plan = resolve_plan(config.kernel_backend,
                                        b=self.b_max, k=config.k,
                                        d=self._dim, bounds=config.bounds)
        self.state = self._place_state(self._host_init_state(C0))

    # -- layout hooks (overridden by _XLRun / _MultiHostRun) ----------------

    def _put_global(self, arr, spec) -> jax.Array:
        """Place a host/local array onto the mesh as ``spec`` says."""
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def _fetch(self, arr) -> np.ndarray:
        """A mesh-placed array back on the host (single-process: free)."""
        return np.asarray(arr)

    def _stat_specs(self) -> ClusterStats:
        """PartitionSpec pytree of the cluster stats (replicated here;
        the XL engine k-shards them over ``model_axis``)."""
        return ClusterStats(C=P(), S=P(), v=P(), sse=P(), p=P())

    def _elkan_spec(self):
        """Spec of the per-(i, j) elkan lower-bound matrix (rows follow
        the points; the k column is replicated here, model-sharded on
        the XL engine)."""
        return P(self._config.data_axes, None)

    def _state_specs(self, with_elkan: bool) -> KMeansState:
        row = P(self._config.data_axes)
        return KMeansState(
            stats=self._stat_specs(),
            points=PointState(a=row, d=row, lb=row),
            elkan=(ElkanBounds(l=self._elkan_spec()) if with_elkan
                   else None),
            round=P())

    def _place_state(self, state: KMeansState) -> KMeansState:
        specs = self._state_specs(state.elkan is not None)
        return jax.tree.map(self._put_global, state, specs)

    def _place_data(self, X: np.ndarray) -> jax.Array:
        lay = self._layout
        if lay.n_storage > self.n_points:
            X = np.concatenate(
                [X, np.repeat(X[:1], lay.n_storage - self.n_points,
                              axis=0)])
        N, s = lay.n_storage, self.n_shards
        Xh = X[lay.perm].reshape(N // s, s, -1).transpose(1, 0, 2)
        return self._put_global(jnp.asarray(Xh.reshape(N, -1)),
                                P(self._config.data_axes, None))

    # -- out-of-core placement (store-backed fits) --------------------------
    # The data buffer starts as zeros and is filled to the current
    # nested prefix on demand: `nested_step` calls `_ensure_prefix(b)`,
    # which fetches only storage rows [filled, b) of every shard — the
    # "reuse old, append new" schedule as disk reads. Fetches run in
    # fixed-size per-shard segments so host memory in flight stays
    # bounded and the donated update jit compiles one full-segment
    # executable plus a handful of ragged tails.

    #: per-shard rows per fetch segment (host rows in flight per update
    #: = _IO_SEG_ROWS * n_shards on single-process meshes)
    _IO_SEG_ROWS = 8192

    def _data_spec(self):
        return P(self._config.data_axes, None)

    def _zeros_data(self) -> jax.Array:
        """The empty (n_storage, d) buffer, assembled from per-device
        zero pieces — no process ever materialises the global shape."""
        shape = (self._N, self._dim)
        sh = NamedSharding(self._mesh, self._data_spec())
        pieces = [
            jax.device_put(np.zeros(_slice_shape(idx, shape), np.float32),
                           dev)
            for dev, idx in
            sh.addressable_devices_indices_map(shape).items()]
        return jax.make_array_from_single_device_arrays(shape, sh, pieces)

    def _fetch_block(self, shards: np.ndarray, lo: int, hi: int):
        """Storage rows [lo, hi) of the given shards, host-side float32
        of shape (len(shards), hi - lo, d).

        All requested shards come off the ChunkStore in ONE `block`
        call. Under the round-robin layout every chunk holds rows of
        every shard, so a per-shard loop would reload each covering
        chunk once per shard (the segment can span more chunks than the
        LRU keeps); fetched together, each chunk of the frontier is
        read once — and the prefix-delta schedule then reads the store
        about once per fit, not once per round.
        """
        return self._src.block(shards, lo, hi).astype(np.float32,
                                                      copy=False)

    def _ensure_prefix(self, b: int) -> None:
        if self._src is None or b <= self._filled:
            return
        with self._obs.span("ingest", rows=b - self._filled):
            shape, sh = self._Xd.shape, self._Xd.sharding
            rps = shape[0] // self.n_shards    # storage rows per shard
            # shard id held by each addressable piece (this process's
            # devices only on multihost; replicas repeat under the XL
            # engine's model axis and each replica is written in place)
            owned = [(s.index[0].start or 0) // rps
                     for s in self._Xd.addressable_shards]
            uniq, inv = np.unique(np.asarray(owned), return_inverse=True)
            lo = self._filled
            while lo < b:
                hi = min(b, lo + self._IO_SEG_ROWS)
                blk = self._fetch_block(uniq, lo, hi)
                pieces = [
                    _piece_update(s.data,
                                  jax.device_put(blk[inv[j]], s.device),
                                  np.int32(lo))
                    for j, s in enumerate(self._Xd.addressable_shards)]
                self._Xd = jax.make_array_from_single_device_arrays(
                    shape, sh, pieces)
                lo = hi
            self._filled = b
            # warm the chunks of the NEXT doubling while this round
            # computes
            self._src.prefetch_positions(
                b * self.n_shards,
                min(2 * b, self.b_max) * self.n_shards)

    def store_metrics(self):
        if self._src is None:
            return None
        return self._src.store.metrics.to_dict()

    def _host_init_state(self, C0: np.ndarray) -> KMeansState:
        """The paper's initial state, built host-side.

        Mirrors `core.state.init_state` value for value; constructed
        from numpy because a multi-process data array cannot be sliced
        for C0 on the host (every process already holds X).
        """
        k, N = self._config.k, self._N
        stats = ClusterStats(
            C=C0, S=np.zeros((k, self._dim), np.float32),
            v=np.zeros((k,), np.float32), sse=np.zeros((k,), np.float32),
            p=np.zeros((k,), np.float32))
        points = PointState(a=np.full((N,), -1, np.int32),
                            d=np.zeros((N,), np.float32),
                            lb=np.zeros((N,), np.float32))
        elkan = (ElkanBounds(l=np.zeros((N, k), np.float32))
                 if self._config.bounds == "elkan" else None)
        return KMeansState(stats=stats, points=points, elkan=elkan,
                           round=np.zeros((), np.int32))

    # -- round executors ----------------------------------------------------

    def nested_step(self, state, b, capacity):
        from repro.core.distributed import make_sharded_round
        self._ensure_prefix(b)
        round_fn = make_sharded_round(
            self._mesh, self._config.data_axes, b_local=b,
            rho=self._config.rho, bounds=self._config.bounds,
            capacity=capacity, use_shalf=self._config.use_shalf,
            n_real=self._n_real, plan=self.kernel_plan)
        return round_fn(self._Xd, state)

    def eval_mse(self, state):
        if self._Xv is None:
            return None
        return float(full_mse(self._Xv, state.stats.C))

    # -- streaming (estimator.partial_fit) ----------------------------------

    def place_stats(self, state, stats):
        placed = jax.tree.map(self._put_global, stats, self._stat_specs())
        return dataclasses.replace(state, stats=placed)

    # -- checkpointing ------------------------------------------------------
    # storage row shard*(N/s)+i holds shuffle position i*s+shard, so
    # canonical order is storage gathered, permuted by _pos, pads cut.

    def _canon(self, arr) -> np.ndarray:
        h = self._fetch(arr)
        out = np.empty_like(h)
        out[self._pos] = h
        return out[:self.n_points]

    def capture(self, state):
        tree = {
            "stats": jax.tree.map(self._fetch, state.stats),
            "a": self._canon(state.points.a),
            "d": self._canon(state.points.d),
            "lb": self._canon(state.points.lb),
            "round": self._fetch(state.round),
        }
        if state.elkan is not None:
            tree["elkan_l"] = self._canon(state.elkan.l)
        meta = {"engine": self._engine_name, "n_shards": self.n_shards,
                "n_points": self.n_points, "has_mb": False,
                "has_elkan": state.elkan is not None}
        return tree, meta

    def _canonical_proto(self, meta):
        """Zero pytree with the canonical checkpoint shapes/dtypes."""
        k, d = self._config.k, self._dim
        n = self.n_points
        proto = {
            "stats": ClusterStats(C=np.zeros((k, d), np.float32),
                                  S=np.zeros((k, d), np.float32),
                                  v=np.zeros((k,), np.float32),
                                  sse=np.zeros((k,), np.float32),
                                  p=np.zeros((k,), np.float32)),
            "a": np.zeros((n,), np.int32),
            "d": np.zeros((n,), np.float32),
            "lb": np.zeros((n,), np.float32),
            "round": np.zeros((), np.int32),
        }
        if meta.get("has_elkan"):
            proto["elkan_l"] = np.zeros((n, k), np.float32)
        return proto

    def _read_canonical(self, store, step, meta):
        """The canonical host tree off the disk (hook: the multihost run
        reads on the coordinator and broadcasts)."""
        got = store.restore(self._canonical_proto(meta), step=step)
        return jax.tree.map(np.asarray, got)

    def restore(self, store, step, meta):
        want_elkan = self._config.bounds == "elkan"
        if meta.get("has_elkan") and not want_elkan:
            raise ValueError(
                "checkpoint carries elkan bounds but this config does "
                "not use bounds='elkan'")
        if want_elkan and not meta.get("has_elkan"):
            raise ValueError(
                "config uses bounds='elkan' but the checkpoint carries "
                "no elkan bound state")
        host = self._read_canonical(store, step, meta)

        row = P(self._config.data_axes)

        # per-point leaves come back canonical; re-pad + re-interleave
        # for THIS mesh's shard count, then place per the layout specs
        def place(h, fill, spec):
            h = np.asarray(h)
            full = np.full((self._N,) + h.shape[1:], fill, h.dtype)
            full[:self.n_points] = h
            return self._put_global(full[self._pos], spec)

        stats = jax.tree.map(self._put_global, host["stats"],
                             self._stat_specs())
        points = PointState(a=place(host["a"], -1, row),
                            d=place(host["d"], 0.0, row),
                            lb=place(host["lb"], 0.0, row))
        elkan = (ElkanBounds(l=place(host["elkan_l"], 0.0,
                                     self._elkan_spec()))
                 if want_elkan else None)
        return KMeansState(stats=stats, points=points, elkan=elkan,
                           round=self._put_global(host["round"], P()))


class MeshEngine:
    """Multi-device engine: points row-sharded, cluster stats replicated.

    The S/v/sse deltas are psum-reduced inside the round, so the stats —
    and therefore the controller's growth decision — are bit-identical
    on every shard with no host round-trip. Only the nested (gb/tb)
    family is supported; `FitConfig.__post_init__` enforces this.
    """

    def __init__(self, mesh):
        self.mesh = mesh

    def begin(self, X, config: FitConfig, *, X_val=None,
              init_C=None, obs=None) -> EngineRun:
        run = _MeshRun(X, config, self.mesh, X_val, init_C)
        run.bind_obs(obs)
        return run
