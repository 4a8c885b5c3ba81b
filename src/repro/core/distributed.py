"""Distributed nested mini-batch k-means: shard_map over the device mesh.

Layout (see DESIGN.md §3):
  * points row-sharded over the data axes (("pod","data") on the
    production mesh). Each shard holds a contiguous slice of the
    PRE-SHUFFLED dataset, so the nested-prefix property holds per shard
    and the global batch of size b is the union of per-shard prefixes of
    size b / n_shards.
  * cluster stats replicated — S/v/sse deltas are psum'ed inside the round
    (rounds.nested_round(data_axes=...)), making the stats, centroids and
    the growth decision bit-identical on every shard with no host
    round-trip.
  * for very large k (kmeans_xl: k=4096) the centroids are additionally
    sharded over "model": each model shard scans its k-slice with the
    fused top-2 kernel, the per-shard (d1, d2, idx) triples — 3 floats per
    point, tiny — are all-gathered over "model" and folded, and the S
    delta is psum_scatter'ed back to the k-shards.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import controller, rounds
from repro.core.state import (ClusterStats, ElkanBounds, KMeansState,
                              PointState, RoundInfo)
from repro.kernels import ops


# --------------------------------------------------------------------------
# replicated-centroid engine (paper-scale k)
# --------------------------------------------------------------------------

def per_shard_n_valid(data_axes: Tuple[str, ...], sizes: Tuple[int, ...],
                      n_shards: int, n_real: Optional[int]):
    """This shard's real-row cap, derived INSIDE shard_map (or None).

    Linear shard index, row-major over ``data_axes`` — matches the slice
    order of NamedSharding(mesh, P(data_axes, None)). The up-to-
    ``n_shards - 1`` tail rows of a non-divisible ``n_real`` land on the
    low shards (PR 2 fix); shared by every sharded round factory so the
    tail-row semantics cannot drift between engines.
    """
    if n_real is None:
        return None
    idx = jnp.zeros((), jnp.int32)
    for ax, sz in zip(data_axes, sizes):
        idx = idx * sz + jax.lax.axis_index(ax)
    base, rem = divmod(n_real, n_shards)
    return base + (idx < rem).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def make_sharded_round(mesh: Mesh, data_axes: Tuple[str, ...], *,
                       b_local: int, rho: float, bounds: str = "hamerly2",
                       capacity: Optional[int] = None,
                       use_shalf: bool = True,
                       n_real: Optional[int] = None,
                       plan=None):
    """jit(shard_map(nested_round)) for one (b_local, capacity) bucket.

    ``plan``: the fit's resolved `kernels.plan.KernelPlan` — hashable,
    so it participates in this factory's lru_cache key exactly like the
    bucket statics do.

    ``n_real``: global count of real (non-pad) rows. When it is not a
    multiple of the shard count, the interleaved placement leaves the
    low shards holding one real row in their last storage slot and the
    high shards holding a structural pad there. Each shard derives its
    own real-row count from its linear index over ``data_axes`` and caps
    the active prefix against it (nested_round's ``n_valid``), so every
    real row — and no pad — enters the final full batch. ``None`` keeps
    the unmasked round (divisible N, and the dry-run cost model).
    """
    row = P(data_axes)
    pt_specs = PointState(a=row, d=row, lb=row)
    st_specs = ClusterStats(C=P(), S=P(), v=P(), sse=P(), p=P())
    # the per-(i, j) elkan lower bounds row-shard with the points (the
    # k column stays replicated like C); the n_valid mask keeps pad
    # rows out of the bound updates exactly as for hamerly2
    elkan_specs = (ElkanBounds(l=P(data_axes, None))
                   if bounds == "elkan" else None)
    state_specs = KMeansState(stats=st_specs, points=pt_specs,
                              elkan=elkan_specs, round=P())
    info_specs = RoundInfo(**{f.name: P() for f in
                              dataclasses.fields(RoundInfo)})

    sizes = tuple(int(mesh.shape[a]) for a in data_axes)
    n_shards = 1
    for s in sizes:
        n_shards *= s

    def fn(Xs, st):
        n_valid = per_shard_n_valid(data_axes, sizes, n_shards, n_real)
        return rounds.nested_round(
            Xs, st, b=b_local, rho=rho, bounds=bounds, capacity=capacity,
            use_shalf=use_shalf, plan=plan, data_axes=data_axes,
            n_valid=n_valid)

    shardmapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(data_axes, None), state_specs),
        out_specs=(state_specs, info_specs), check_vma=False)
    return jax.jit(shardmapped)


def shard_state(state: KMeansState, mesh: Mesh,
                data_axes: Tuple[str, ...]) -> KMeansState:
    """Place a host state onto the mesh with the engine's layout."""
    row = NamedSharding(mesh, P(data_axes))
    rep = NamedSharding(mesh, P())
    points = PointState(
        a=jax.device_put(state.points.a, row),
        d=jax.device_put(state.points.d, row),
        lb=jax.device_put(state.points.lb, row))
    stats = jax.tree.map(lambda x: jax.device_put(x, rep), state.stats)
    return KMeansState(stats=stats, points=points, elkan=None,
                       round=jax.device_put(state.round, rep))


def fit_distributed(X,
                    k: int,
                    mesh: Mesh,
                    *,
                    data_axes: Tuple[str, ...] = ("data",),
                    rho: float = float("inf"),
                    b0: int = 5000,
                    bounds: str = "hamerly2",
                    max_rounds: int = 1000,
                    seed: int = 0,
                    use_shalf: bool = True,
                    on_round=None):
    """DEPRECATED multi-device entry point — shim over `repro.api`.

    The sharded host loop that used to live here is now
    `repro.api.loop.run_loop` driving a `MeshEngine`; this wrapper
    keeps the historical signature and dict telemetry. Semantically
    identical to driver.fit(algorithm="tb") modulo the batch
    composition: the global batch is the union of equal per-shard
    prefixes of one global shuffle (vs a global prefix). Both are
    uniform samples; tests check single-shard equivalence exactly.
    """
    from repro import api

    config = api.FitConfig(
        k=k, algorithm="tb", rho=rho, b0=b0, bounds=bounds,
        max_rounds=max_rounds, seed=seed, use_shalf=use_shalf,
        backend="mesh", data_axes=tuple(data_axes),
        # the pre-api sharded loop used a smaller capacity floor and
        # declared convergence on the first quiet round
        capacity_floor=256, converge_patience=1)
    cb = (lambda rec: on_round(rec.to_dict())) if on_round else None
    out = api.fit(X, config, mesh=mesh, on_round=cb)
    from repro.core.driver import FitResult
    return FitResult.from_outcome(out, algorithm=f"tb-dist[{bounds}]")


# --------------------------------------------------------------------------
# sharded-centroid assignment (k over "model") — the kmeans_xl path
# --------------------------------------------------------------------------

def _fold_top2(d1a, d2a, ia, d1b, d2b, ib):
    """Combine two (min, 2nd-min, argmin) triples.

    Ties on the minimum break toward the LOWER global index, which makes
    the fold associative and commutative: tree folds, sequential folds
    and a single-device argmin over the concatenated centroids all pick
    the same winner, so shard count never changes an assignment.
    """
    take_b = (d1b < d1a) | ((d1b == d1a) & (ib < ia))
    new1 = jnp.minimum(d1a, d1b)
    newi = jnp.where(take_b, ib, ia)
    new2 = jnp.minimum(jnp.maximum(d1a, d1b), jnp.minimum(d2a, d2b))
    return new1, new2, newi


def assign_top2_sharded(x: jax.Array, C_local: jax.Array, *,
                        model_axis: str, k_offset: jax.Array,
                        backend: Optional[str] = None, plan=None):
    """Top-2 nearest over model-sharded centroids (inside shard_map).

    Each model shard scans its (k_local, d) slice, then the per-shard
    triples are all-gathered over ``model_axis`` (3 floats + 1 int per
    point per shard) and combined with a log-depth tree fold — the
    per-point reduction is O(log m) fold steps instead of the m-1 of a
    sequential left fold.

    Returns ``(a, d1_sq, d2_sq)`` with GLOBAL centroid indices and
    SQUARED distances — the exact units of `ops.assign_top2`, so the two
    are drop-in interchangeable and callers take one sqrt at the
    boundary. Ties on the minimum distance resolve to the lowest global
    index, matching `jnp.argmin` on the unsharded centroid block.
    """
    a_loc, d1_loc, d2_loc = ops.assign_top2(x, C_local, plan=plan,
                                            backend=backend)
    a_glob = a_loc + k_offset
    d1s = jax.lax.all_gather(d1_loc, model_axis)       # (m, b)
    d2s = jax.lax.all_gather(d2_loc, model_axis)
    ias = jax.lax.all_gather(a_glob, model_axis)
    while d1s.shape[0] > 1:
        half = d1s.shape[0] // 2
        d1, d2, ia = _fold_top2(
            d1s[:half], d2s[:half], ias[:half],
            d1s[half:2 * half], d2s[half:2 * half], ias[half:2 * half])
        if d1s.shape[0] % 2:           # odd: carry the tail row over
            d1 = jnp.concatenate([d1, d1s[2 * half:]])
            d2 = jnp.concatenate([d2, d2s[2 * half:]])
            ia = jnp.concatenate([ia, ias[2 * half:]])
        d1s, d2s, ias = d1, d2, ia
    return ias[0].astype(jnp.int32), d1s[0], d2s[0]


def xl_round_body(x, C_local, S_local, v_local, *, k: int,
                  data_axes: Tuple[str, ...], model_axis: str,
                  rho: float = float("inf")):
    """One production round with points sharded over data axes AND
    centroids sharded over the model axis (the kmeans_xl dry-run step).

    Stateless-bounds variant (first / dense round): exhaustive sharded
    top-2, fresh S/v via one-hot-matmul cluster sums reduced with
    psum(data) + psum_scatter(model). Returns the updated local centroid
    shard and telemetry. All returned distances (``d``, ``d2``) are
    EUCLIDEAN — `assign_top2_sharded` returns squared distances and this
    boundary takes the sqrt for both, so the output tuple never mixes
    units. ``rho`` is the growth-controller threshold (Alg. 6);
    ``float("inf")`` keeps the gb-inf/tb-inf degenerate rule.

    The loop-driven nested-prefix variant (delta S/v, bounds, n_valid
    masking) lives in `repro.core.distributed_xl.xl_nested_round`.
    """
    k_local = C_local.shape[0]
    ax_idx = jax.lax.axis_index(model_axis)
    k_offset = ax_idx * k_local

    a, d1, d2sq = assign_top2_sharded(x, C_local, model_axis=model_axis,
                                      k_offset=k_offset)
    d = jnp.sqrt(jnp.maximum(d1, 0.0))
    d2 = jnp.sqrt(jnp.maximum(d2sq, 0.0))

    # full-k local partials. x (and the folded a) are REPLICATED over the
    # model axis, so each model shard's partial already agrees across the
    # axis: slice out the local k-range for free, then psum only the
    # (k_local, d) slice over the data axes — the data all-reduce volume
    # drops by the model-axis size versus reducing full k everywhere.
    S_full, v_full = ops.cluster_sum(x, a, k)
    sse_full = jax.ops.segment_sum(d * d, a, num_segments=k)
    S_new = jax.lax.dynamic_slice_in_dim(S_full, k_offset, k_local, 0)
    v_new = jax.lax.dynamic_slice_in_dim(v_full, k_offset, k_local, 0)
    sse_new = jax.lax.dynamic_slice_in_dim(sse_full, k_offset, k_local, 0)
    S_new = jax.lax.psum(S_new, data_axes)
    v_new = jax.lax.psum(v_new, data_axes)
    sse_new = jax.lax.psum(sse_new, data_axes)

    safe_v = jnp.maximum(v_new, 1.0)
    C_new = jnp.where((v_new > 0.0)[:, None], S_new / safe_v[:, None],
                      C_local)
    p_local = jnp.sqrt(jnp.sum((C_new - C_local) ** 2, axis=1))
    # growth controller needs global per-cluster stats (tiny vectors)
    p_all = jax.lax.all_gather(p_local, model_axis, tiled=True)
    v_all = jax.lax.all_gather(v_new, model_axis, tiled=True)
    sse_all = jax.lax.all_gather(sse_new, model_axis, tiled=True)
    grow, r_med = controller.should_grow(sse_all, v_all, p_all, rho=rho)
    mse = jax.lax.psum(jnp.sum(d * d), data_axes) / \
        jax.lax.psum(jnp.asarray(x.shape[0], jnp.float32), data_axes)
    return C_new, S_new, v_new, a, d, d2, grow, r_med, mse


def dp_round_body(x, C, *, data_axes: Tuple[str, ...],
                  rho: float = float("inf"), use_pallas: bool = False):
    """Optimized production round: pure data parallelism, C replicated.

    For k up to ~10^4 the centroid block is VMEM-resident (k=4096 x
    d=1024 bf16 = 8 MiB), so sharding points over EVERY mesh axis and
    replicating C beats centroid sharding: assignment intensity is 2k
    FLOPs per 4 bytes of x — firmly compute-bound — and the only
    collective is the (k, d) psum of S/v/sse. On TPU the whole round is
    the fused single-X-pass Pallas kernel (kernels/fused_round.py).
    """
    if use_pallas:
        from repro.kernels.fused_round import fused_round_pallas
        from repro.kernels.plan import resolve_plan
        plan = resolve_plan("pallas", b=x.shape[0], k=C.shape[0],
                            d=x.shape[1])
        a, d1, d2, S_loc, v_loc, sse_loc = fused_round_pallas(
            x, C, bn=plan.row_tile(x.shape[0]), interpret=plan.interpret)
    else:
        a, d1sq, _ = ops.assign_top2(x, C)
        d1 = d1sq
        S_loc, v_loc = ops.cluster_sum(x, a, C.shape[0])
        sse_loc = jax.ops.segment_sum(d1, a, num_segments=C.shape[0])
    d = jnp.sqrt(jnp.maximum(d1, 0.0))
    S = jax.lax.psum(S_loc, data_axes)
    v = jax.lax.psum(v_loc, data_axes)
    sse = jax.lax.psum(sse_loc, data_axes)
    safe_v = jnp.maximum(v, 1.0)
    C_new = jnp.where((v > 0.0)[:, None], S / safe_v[:, None], C)
    p = jnp.sqrt(jnp.sum((C_new - C) ** 2, axis=1))
    grow, r_med = controller.should_grow(sse, v, p, rho=rho)
    mse = jax.lax.psum(jnp.sum(d * d), data_axes) / jax.lax.psum(
        jnp.asarray(x.shape[0], jnp.float32), data_axes)
    return C_new, S, v, a, d, grow, r_med, mse


@functools.lru_cache(maxsize=None)
def make_dp_round(mesh: Mesh, *, rho: float = float("inf"),
                  use_pallas: bool = False):
    """jit(shard_map) data-parallel round over ALL mesh axes.

    ``rho`` is a static cache key like `make_sharded_round`'s: the
    config's threshold reaches the controller instead of a hardcoded
    ``float("inf")``.
    """
    axes = tuple(mesh.axis_names)
    fn = functools.partial(dp_round_body, data_axes=axes, rho=rho,
                           use_pallas=use_pallas)
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(None, None), P(None, None), P(None),
                   P(axes), P(axes), P(), P(), P()), check_vma=False)
    return jax.jit(sm)


@functools.lru_cache(maxsize=None)
def make_xl_round(mesh: Mesh, *, k: int,
                  data_axes: Tuple[str, ...] = ("data",),
                  model_axis: str = "model",
                  rho: float = float("inf")):
    """jit(shard_map) of the sharded-centroid production round.

    Kept as the centroid-sharded variant for k too large to replicate
    (k*d beyond VMEM, ~10^5+ centroids); for kmeans_xl (k=4096) the
    data-parallel ``make_dp_round`` dominates it — see §Perf. ``rho``
    is a static cache key threading the config's growth threshold to
    the controller. The loop-driven engine over this layout is
    `repro.api.engines.xl.XLEngine` (see `core.distributed_xl`)."""
    row = P(data_axes)
    kshard = P(model_axis)

    fn = functools.partial(xl_round_body, k=k, data_axes=data_axes,
                           model_axis=model_axis, rho=rho)
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(data_axes, None), P(model_axis, None),
                  P(model_axis, None), kshard),
        out_specs=(P(model_axis, None), P(model_axis, None), kshard,
                   row, row, row, P(), P(), P()), check_vma=False)
    return jax.jit(sm)
