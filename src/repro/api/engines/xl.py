"""`XLEngine` — centroids sharded over the model axis (kmeans_xl scale)."""
from __future__ import annotations

from jax.sharding import PartitionSpec as P

from repro.api.config import FitConfig
from repro.api.engines.base import EngineRun
from repro.api.engines.mesh import _MeshRun


class _XLRun(_MeshRun):
    """A `_MeshRun` whose cluster stats are sharded over ``model_axis``.

    Data placement, b units (per-data-shard rows), the n_valid tail mask
    and the canonical checkpoint layout are all inherited from the mesh
    run — checkpoints are written with FULL (k, d) stats, so an XL
    checkpoint restores elastically onto local/mesh engines and onto any
    model-axis size that divides k, and vice versa. Only the state
    placement and the compiled round differ.
    """
    _engine_name = "xl"
    # the sharded round keeps its own full-prefix delta (`_delta_sv_xl`)
    delta_over_buffer = False

    def __init__(self, X, config: FitConfig, mesh, X_val, init_C):
        if config.model_axis not in mesh.shape:
            raise ValueError(
                f"backend='xl' needs mesh axis "
                f"{config.model_axis!r} (config.model_axis) to shard "
                f"the centroids over, but the mesh only has axes "
                f"{tuple(mesh.axis_names)}")
        m = int(mesh.shape[config.model_axis])
        if config.k % m:
            raise ValueError(
                f"backend='xl' shards the k={config.k} centroids over "
                f"mesh axis {config.model_axis!r} of size {m}; k must "
                f"divide evenly")
        super().__init__(X, config, mesh, X_val, init_C)

    def _stat_specs(self):
        from repro.core.distributed_xl import xl_state_specs
        return xl_state_specs(self._config.data_axes,
                              self._config.model_axis).stats

    def _elkan_spec(self):
        # one (rows_local, k_local) block per device: rows follow the
        # data shards, the k column follows the centroid shards
        return P(self._config.data_axes, self._config.model_axis)

    def nested_step(self, state, b, capacity):
        from repro.core.distributed_xl import make_xl_nested_round
        self._ensure_prefix(b)   # out-of-core: no-op on in-memory fits
        round_fn = make_xl_nested_round(
            self._mesh, self._config.data_axes,
            model_axis=self._config.model_axis, b_local=b,
            rho=self._config.rho, bounds=self._config.bounds,
            capacity=capacity, use_shalf=self._config.use_shalf,
            n_real=self._n_real, plan=self.kernel_plan)
        return round_fn(self._Xd, state)


class XLEngine:
    """Centroid-sharded engine: points over data axes, k over model.

    The regime past `MeshEngine`: when k*d no longer replicates (the
    ~10^5-centroid massive-data setting), each model shard scans only
    its k-slice with the fused top-2 kernel, the per-point top-2 triples
    are tree-folded over the model axis, and the S/v deltas are
    psum_scatter'ed so no device ever materialises full-k statistics.
    Drives the same `run_loop` (growth, overflow retry, patience,
    checkpoints) as every other engine.
    """

    def __init__(self, mesh):
        self.mesh = mesh

    def begin(self, X, config: FitConfig, *, X_val=None,
              init_C=None, obs=None) -> EngineRun:
        run = _XLRun(X, config, self.mesh, X_val, init_C)
        run.bind_obs(obs)
        return run
