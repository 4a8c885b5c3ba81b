"""`MultiHostEngine` — the mesh engine across `jax.distributed` processes.

Every process runs the SAME host loop over the SAME global schedule
(see `repro.api.loop`'s replication invariant); this module only
changes WHERE arrays live and HOW the host sees them:

  * placement: a process cannot `device_put` onto devices it does not
    own, so `_put_global` assembles global arrays from process-local
    single-device shards (`jax.make_array_from_single_device_arrays`).
    The data placement slices each process's rows straight out of the
    shared `nested_shard_layout` (`ShardLayout.shard_orig_rows`): a
    process materialises only its own shards' rows, never the padded
    permuted copy of the whole dataset.
  * host views: a row-sharded global array is not addressable from any
    one process, so `_fetch` replicates it with a jitted identity
    (compiling to one all-gather) and reads the local copy. Replicated
    arrays (stats, RoundInfo scalars) are read directly — every
    process holds the full value.
  * checkpoints: only process 0 writes (`is_coordinator`); `capture`'s
    gathers and `restore`'s coordinator-read + `broadcast_one_to_all`
    are collectives every process joins, bracketed by the loop's
    `barrier()` calls.

Bit-compatibility: on ONE process this run places the same rows on the
same devices as `_MeshRun` and executes the same
`make_sharded_round` executable, so a single-process multihost fit is
bit-identical (centroids, labels, per-point state, schedule) to the
mesh engine — asserted by scripts/smoke_multihost.py, which also spawns
a real 2-process CPU cluster with a local coordinator.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.config import FitConfig
from repro.api.engines.base import EngineRun
from repro.api.engines.mesh import _MeshRun


class _MultiHostRun(_MeshRun):
    _engine_name = "multihost"

    def __init__(self, X, config: FitConfig, mesh, X_val, init_C):
        # one executable per aval: the replicating identity behind
        # _fetch (an all-gather over whatever the input's sharding is)
        self._replicate = jax.jit(
            lambda t: t, out_shardings=NamedSharding(mesh, P()))
        super().__init__(X, config, mesh, X_val, init_C)

    # -- layout hooks -------------------------------------------------------

    def _put_global(self, arr, spec):
        sh = NamedSharding(self._mesh, spec)
        arr = np.asarray(arr)
        pieces = [
            jax.device_put(arr[idx], dev)
            for dev, idx in
            sh.addressable_devices_indices_map(arr.shape).items()]
        return jax.make_array_from_single_device_arrays(
            arr.shape, sh, pieces)

    def _place_data(self, X):
        # per-process row placement: each local device holds exactly one
        # data shard's slice; pull that shard's rows straight from the
        # layout instead of materialising the full padded permutation
        lay = self._layout
        shape = (lay.n_storage, self._dim)
        sh = NamedSharding(self._mesh, P(self._config.data_axes, None))
        rps = lay.rows_per_shard
        pieces = []
        for dev, idx in sh.addressable_devices_indices_map(shape).items():
            s = (idx[0].start or 0) // rps
            rows = lay.shard_orig_rows(s)   # (rps,) caller rows, -1 = pad
            Xl = X[np.where(rows >= 0, rows, 0)]  # pads are X[0] copies
            pieces.append(jax.device_put(jnp.asarray(Xl), dev))
        return jax.make_array_from_single_device_arrays(shape, sh, pieces)

    # out-of-core `_ensure_prefix` needs no override: the base run
    # derives shard ids from `_Xd.addressable_shards`, which on a
    # multi-process mesh are exactly this process's devices — each
    # process reads only its own shards' rows off its own store handle.

    def _fetch(self, arr):
        if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
            return np.asarray(arr)
        if arr.sharding.is_fully_replicated:
            return np.asarray(arr.addressable_data(0))
        # row-sharded across processes: all-gather, read the local copy
        return np.asarray(self._replicate(arr).addressable_data(0))

    # -- host views ---------------------------------------------------------

    def eval_mse(self, state):
        if self._Xv is None:
            return None
        # fetch C first: X_val lives process-locally, and one jit cannot
        # mix a process-local array with a multi-process global one
        from repro.core.state import full_mse
        return float(full_mse(self._Xv,
                              jnp.asarray(self._fetch(state.stats.C))))

    def host_points(self, state):
        return self._fetch(state.points.a)

    def fetch_stats(self, state):
        return jax.tree.map(self._fetch, state.stats)

    # -- process awareness --------------------------------------------------

    @property
    def is_coordinator(self) -> bool:
        return jax.process_index() == 0

    def barrier(self) -> None:
        if jax.process_count() == 1:
            return
        multihost_utils.sync_global_devices("repro.api.loop")

    def sync_flag(self, flag: bool) -> bool:
        if jax.process_count() == 1:
            return bool(flag)
        return bool(int(multihost_utils.broadcast_one_to_all(
            np.int32(bool(flag)))))

    def resolve_resume(self, store):
        if jax.process_count() == 1:
            return super().resolve_resume(store)
        # the coordinator's filesystem is the source of truth: step and
        # metadata are broadcast so every process resumes the same run
        # even when the checkpoint directory is not shared
        payload = b""
        if self.is_coordinator:
            step, extra = super().resolve_resume(store)
            if extra is not None:
                payload = json.dumps(extra).encode()
            head = np.array([step if step is not None else -1,
                             len(payload)], np.int64)
        else:
            head = np.zeros((2,), np.int64)
        head = multihost_utils.broadcast_one_to_all(head)
        step, n = int(head[0]), int(head[1])
        extra = None
        if n:
            buf = np.zeros((n,), np.uint8)
            if self.is_coordinator:
                buf[:] = np.frombuffer(payload, np.uint8)
            # broadcast upcasts for its psum on some jax versions —
            # force the byte dtype back before decoding
            buf = np.asarray(multihost_utils.broadcast_one_to_all(buf),
                             dtype=np.uint8)
            extra = json.loads(buf.tobytes().decode())
        return (None, None) if step < 0 else (step, extra)

    def _read_canonical(self, store, step, meta):
        if jax.process_count() == 1:
            return super()._read_canonical(store, step, meta)
        proto = self._canonical_proto(meta)
        host = (super()._read_canonical(store, step, meta)
                if self.is_coordinator else proto)
        got = multihost_utils.broadcast_one_to_all(host)
        # pin dtypes: the broadcast may upcast narrow leaves for its psum
        return jax.tree.map(
            lambda g, p: np.asarray(g, dtype=np.asarray(p).dtype),
            got, proto)


class MultiHostEngine:
    """`jax.distributed` engine: the mesh schedule at pod scale.

    Build one per process (same config everywhere) and call `begin` with
    the SAME dataset on every process; the engine places each process's
    rows, and the shared `run_loop` — whose control flow is replicated
    by construction — drives the fit with no cross-process coordination
    beyond the collectives inside the compiled round.

    ``mesh`` may be omitted: `begin` then initialises `jax.distributed`
    from the config's coordinator fields (if set and not already up)
    and builds a flat data mesh over every device of every process
    (`repro.launch.mesh.make_multihost_mesh`).
    """

    def __init__(self, mesh=None):
        self.mesh = mesh

    def begin(self, X, config: FitConfig, *, X_val=None,
              init_C=None, obs=None) -> EngineRun:
        if self.mesh is None:
            from repro.launch.mesh import (ensure_multihost_initialized,
                                           make_multihost_mesh)
            ensure_multihost_initialized(config)
            self.mesh = make_multihost_mesh(config.data_axes)
        run = _MultiHostRun(X, config, self.mesh, X_val, init_C)
        run.bind_obs(obs)
        return run
