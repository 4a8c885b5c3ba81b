"""Loop-driven centroid-sharded nested rounds (the kmeans_xl engine core).

`core.distributed.make_xl_round` is a stateless dense round: every call
re-assigns every point against fresh S/v. This module is the
nested-prefix counterpart that `repro.api.engines.xl.XLEngine` drives
through the shared host loop (`run_loop`): per-shard prefix batching
with ``n_valid`` masking, previously-seen-point delta S/v, Hamerly
bounding, growth, overflow retry and checkpointing — the full Alg. 6/9
schedule at centroid counts too large to replicate.

Layout (extends DESIGN.md §3 with a sharded model dimension):
  * points row-sharded over ``data_axes`` exactly like the mesh engine
    (`data.pipeline.nested_shard_layout` placement; the union of
    per-shard prefixes of size b is the global shuffle prefix), and
    REPLICATED over ``model_axis``.
  * cluster stats sharded over ``model_axis``: each model shard owns the
    (k_local, d) slice of C/S and the (k_local,) slices of v/sse/p,
    replicated over the data axes.
  * assignment: each model shard scans its k-slice with the fused top-2
    kernel; the per-shard (d1, d2, idx) triples are all-gathered over
    ``model_axis`` and tree-folded (`assign_top2_sharded`), so ``a``
    holds GLOBAL centroid indices and is replica-consistent over model.
  * delta S/v: the local batch rows are split into ``m`` chunks, one per
    model shard; each shard computes full-k partial sums over ITS row
    chunk only (an m-fold FLOP cut versus every shard summing every
    row), then one psum_scatter over ``model_axis`` simultaneously
    reduces the chunks and scatters the k-slices — each k-shard receives
    exactly its own slice — and a psum over ``data_axes`` completes the
    global delta. sse refreshes the same way.
  * the growth controller needs global per-cluster stats: the tiny
    (k_local,) vectors v/sse/p are all-gathered over ``model_axis`` and
    fed to `controller.should_grow` with the CONFIG's rho.

Bit-compatibility: on a 1-device model axis every collective here
collapses to the identity and each compute step mirrors
`rounds.nested_round` operation for operation, so an XLEngine fit on a
single-model-shard mesh reproduces the MeshEngine (and, at one data
shard, the LocalEngine) bit for bit — tested in scripts/smoke_xl.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import controller, rounds
from repro.core.distributed import (_fold_top2, assign_top2_sharded,
                                    per_shard_n_valid)
from repro.core.rounds import _euclid
from repro.core.state import (ClusterStats, ElkanBounds, KMeansState,
                              PointState, RoundInfo, centroid_update)
from repro.kernels import ops, ref
from repro.util import tracecount


# --------------------------------------------------------------------------
# sharded building blocks
# --------------------------------------------------------------------------

def _dist_to_assigned_sharded(x: jax.Array, C_local: jax.Array,
                              a: jax.Array, k_offset: jax.Array,
                              model_axis: str) -> jax.Array:
    """Exact euclidean distance of each point to its assigned centroid.

    The assigned centroid of a point may live on any model shard: each
    shard computes the distance for the points whose GLOBAL assignment
    falls in its k-slice and contributes zero for the rest, and one psum
    over ``model_axis`` assembles the full vector. Never-assigned points
    (``a == -1``) fall outside every slice and come back 0.0 — their
    lanes are dead (``seen`` gates every use downstream).
    """
    k_local = C_local.shape[0]
    a_loc = a - k_offset
    own = (a_loc >= 0) & (a_loc < k_local)
    Cg = C_local[jnp.clip(a_loc, 0, k_local - 1)]
    d2 = jnp.sum((x.astype(jnp.float32) - Cg) ** 2, axis=1)
    return _euclid(jax.lax.psum(jnp.where(own, d2, 0.0), model_axis))


def _half_intercentroid_sharded(C_local: jax.Array, model_axis: str,
                                m: int) -> jax.Array:
    """Hamerly's s(j)/2 for every GLOBAL j, from per-shard k-slices.

    Ring reduction: the (k_local, d) centroid blocks rotate around the
    model axis; at each of the m-1 steps every shard folds the visiting
    block's distances into its running per-centroid minimum. Peak
    memory stays O(k_local * d) — the full (k, d) codebook is never
    materialised on any device, which is the engine's reason to exist.
    (min is exact, so the partitioned fold equals a dense row min bit
    for bit.) The resulting (k_local,) vectors are all-gathered into
    the full (k,) threshold table every shard needs for the bound test.
    """
    k_local = C_local.shape[0]
    # own block first, self-distance masked by global index
    d2_own = ref.pairwise_dist2(C_local, C_local)
    eye = jnp.arange(k_local)
    d2_own = d2_own.at[eye, eye].set(jnp.inf)
    best = jnp.min(d2_own, axis=1)
    block = C_local
    perm = [(i, (i + 1) % m) for i in range(m)]
    for _ in range(m - 1):
        block = jax.lax.ppermute(block, model_axis, perm)
        best = jnp.minimum(best,
                           jnp.min(ref.pairwise_dist2(C_local, block),
                                   axis=1))
    s_half_loc = 0.5 * _euclid(best)
    return jax.lax.all_gather(s_half_loc, model_axis, tiled=True)  # (k,)


def _fold_min_idx(da, ia, db, ib):
    """Combine two (min, argmin) pairs; ties take the LOWER global index
    (associative + commutative, so the fold order cannot change the
    winner — and it matches `jnp.argmin`'s first-minimum rule on the
    unsharded row)."""
    take_b = (db < da) | ((db == da) & (ib < ia))
    return jnp.minimum(da, db), jnp.where(take_b, ib, ia)


def _assign_elkan_xl(x, state, a_prev, valid, *, k_local: int,
                     k_offset, model_axis: str):
    """`rounds._assign_elkan` with the k column sharded over the model
    axis: each shard holds the (b, k_local) slice of the lower-bound
    matrix l and of its C/p slices, runs the bound test locally, and the
    per-shard (min, argmin) candidates are tree-folded into the global
    assignment. Bit-compatible with the local path on a 1-shard model
    axis (every collective collapses to the identity)."""
    C_local = state.stats.C
    seen = a_prev >= 0
    l_dec = state.elkan.l[:x.shape[0]] - state.stats.p[None, :]  # eq. (4)
    d_a = _dist_to_assigned_sharded(x, C_local, a_prev, k_offset,
                                    model_axis)

    d_all = _euclid(ref.pairwise_dist2(x, C_local))     # (b, k_local)
    cols = k_offset + jnp.arange(k_local)[None, :]      # GLOBAL indices
    own = cols == a_prev[:, None]
    compute = (l_dec < d_a[:, None]) & ~own             # bound test
    compute = compute | ~seen[:, None]                  # new pts: all k
    if valid is not None:
        compute = compute & valid[:, None]

    l_new = jnp.where(compute, d_all, l_dec)
    cand = jnp.where(compute, d_all, jnp.inf)
    cand = jnp.where(own & seen[:, None], d_a[:, None], cand)
    # local winner carries its GLOBAL index; fold across model shards
    a_loc = (jnp.argmin(cand, axis=1).astype(jnp.int32) + k_offset)
    d_loc = jnp.min(cand, axis=1)
    ds = jax.lax.all_gather(d_loc, model_axis)          # (m, b)
    ias = jax.lax.all_gather(a_loc, model_axis)
    while ds.shape[0] > 1:
        half = ds.shape[0] // 2
        d, ia = _fold_min_idx(ds[:half], ias[:half],
                              ds[half:2 * half], ias[half:2 * half])
        if ds.shape[0] % 2:            # odd: carry the tail row over
            d = jnp.concatenate([d, ds[2 * half:]])
            ia = jnp.concatenate([ia, ias[2 * half:]])
        ds, ias = d, ia
    a_new, d_new = ias[0].astype(jnp.int32), ds[0]
    # pair computations across the whole k row + the per-point d_a's
    # (pads are never seen, so they add nothing to the second term)
    n_comp = jax.lax.psum(jnp.sum(compute.astype(jnp.int32)),
                          model_axis) \
        + jnp.sum(seen.astype(jnp.int32))
    return a_new, d_new, None, n_comp, jnp.asarray(False), l_new


def _exponion_geom_xl(C_local: jax.Array, model_axis: str, m: int,
                      k_offset: jax.Array):
    """Exponion geometry from per-shard k-slices: (B, s).

    ``B`` is this shard's (k, k_local) block of the inter-centroid
    distance matrix — rows are GLOBAL anchors, columns are the LOCAL
    centroids — assembled with the same ring ppermute as
    `_half_intercentroid_sharded`, so peak memory stays O(k^2 / m) per
    shard (never the full k x k table). ``s`` is the full (k,) nearest-
    other-centroid table (min over local columns, pmin over the model
    axis) — one structure feeds both the Hamerly threshold (s/2) and the
    annulus radius (2*d_a + s), exactly like the local `ExponionGeom`.

    The own diagonal of B is set to an EXACT zero: the anchor must
    always pass its own ``<= R`` test (the matmul distance form can
    leave rounding dust there), which is what makes the union of
    per-shard candidate sets a superset of the exact global annulus.
    """
    k_local = C_local.shape[0]
    k = k_local * m
    ax = jax.lax.axis_index(model_axis)
    cols = jnp.arange(k_local)
    own_rows = k_offset + cols

    B = jnp.zeros((k, k_local), jnp.float32)
    block = C_local
    perm = [(i, (i + 1) % m) for i in range(m)]
    for step in range(m):
        # after `step` rotations this shard holds the block that
        # originated on shard (ax - step) % m — its rows of B
        d_blk = _euclid(ref.pairwise_dist2(block, C_local))
        src = jax.lax.rem(ax - step + m, m)
        B = jax.lax.dynamic_update_slice(
            B, d_blk, (src * k_local, jnp.int32(0)))
        if step < m - 1:
            block = jax.lax.ppermute(block, model_axis, perm)

    B = B.at[own_rows, cols].set(0.0)
    masked = B.at[own_rows, cols].set(jnp.inf)
    s = jax.lax.pmin(jnp.min(masked, axis=1), model_axis)      # (k,)
    return B, s


def _assign_exponion_xl(x, state, a_prev, valid, *, k_local: int,
                        k_offset, model_axis: str, m: int,
                        use_shalf: bool):
    """`rounds._assign_exponion` with the centroids model-sharded.

    Each shard tests its local centroid columns against the EXACT
    annulus (``B[anchor] <= R``) — there is no global sorted neighbour
    table across shards, so each shard counts its block's members
    directly; the union of per-shard candidate sets is the exact
    annulus plus full rows for unseen points, the same set the local
    path's ``rank < m_exact`` mask selects, so labels, centroids, the
    stored lb AND the ``n_recomputed`` pair count are all bit-equal to
    the local/mesh exponion (and labels/centroids to ``bounds="none"``).

    Degenerate rings: when k/m leaves fewer than 4 local centroid
    columns, an annulus test cannot beat scanning the row it would need
    to test — fall back to the elkan-style full local scan for failing
    points and skip building B entirely (the s table still comes from
    the ring reduction for the Hamerly threshold).

    Per-shard (min, 2nd-min, global argmin) triples are tree-folded
    with `distributed._fold_top2` (lowest-global-index tie-break), so
    the fold matches `jnp.argmin` on the unsharded row.
    """
    C_local = state.stats.C
    k = k_local * m
    b = x.shape[0]
    seen = a_prev >= 0
    degenerate = k_local < 4

    p_max = jax.lax.pmax(jnp.max(state.stats.p), model_axis)
    d_a = _dist_to_assigned_sharded(x, C_local, a_prev, k_offset,
                                    model_axis)
    if degenerate:
        B = None
        s_half = _half_intercentroid_sharded(C_local, model_axis, m)
    else:
        B, s = _exponion_geom_xl(C_local, model_axis, m, k_offset)
        s_half = 0.5 * s
    settled, lb_dec, d_a, _n_need = rounds._hamerly_settled(
        x, state, a_prev, valid, use_shalf=use_shalf, p_max=p_max,
        d_assigned=d_a, s_half=s_half)
    needs = ~settled

    if degenerate:
        scan = jnp.broadcast_to(needs[:, None], (b, k_local))
    else:
        anchor = jnp.clip(a_prev, 0, k - 1)
        R = 2.0 * d_a + s[anchor]
        scan = needs[:, None] & ((B[anchor] <= R[:, None])
                                 | ~seen[:, None])
    if valid is not None:
        scan = scan & valid[:, None]

    # candidate top-2 in SQUARED space (the units `assign_top2_sharded`
    # folds in — identical values and tie-breaks), sqrt after the fold
    cand = jnp.where(scan, ref.pairwise_dist2(x, C_local), jnp.inf)
    a_col = jnp.argmin(cand, axis=1).astype(jnp.int32)
    a_loc = a_col + k_offset                         # GLOBAL index
    d1_loc = jnp.min(cand, axis=1)
    rest = jnp.where(jnp.arange(k_local)[None, :] == a_col[:, None],
                     jnp.inf, cand)
    d2_loc = jnp.min(rest, axis=1)

    d1s = jax.lax.all_gather(d1_loc, model_axis)     # (m, b)
    d2s = jax.lax.all_gather(d2_loc, model_axis)
    ias = jax.lax.all_gather(a_loc, model_axis)
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
    a_f, d1, d2 = (ias[0].astype(jnp.int32), _euclid(d1s[0]),
                   _euclid(d2s[0]))

    a_new = jnp.where(settled, a_prev, a_f)
    d_new = jnp.where(settled, d_a, d1)
    lb_new = jnp.where(settled, lb_dec, d2)
    # pair accounting (elkan convention): scanned pairs + the per-seen-
    # point d_a refresh (pads are never seen, so they add nothing)
    n_comp = jax.lax.psum(jnp.sum(scan.astype(jnp.int32)), model_axis) \
        + jnp.sum(seen.astype(jnp.int32))
    return a_new, d_new, lb_new, n_comp, jnp.asarray(False), None


def _chunk_rows(arrs, *, m: int, model_axis: str):
    """Deal the batch rows into ``m`` chunks, one per model shard.

    Rows are padded up to a multiple of ``m`` (the pad weights are zero,
    so padded rows contribute nothing) and model shard i takes chunk i.
    This is what makes the psum_scatter reduction below also an m-fold
    FLOP cut: every shard only cluster-sums b/m rows.
    """
    b = arrs[0].shape[0]
    chunk = -(-b // m)
    pad = m * chunk - b
    ax = jax.lax.axis_index(model_axis)
    out = []
    for a in arrs:
        if pad:
            widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
            a = jnp.pad(a, widths)
        out.append(jax.lax.dynamic_slice_in_dim(a, ax * chunk, chunk, 0))
    return out


def _delta_sv_xl(x, a_prev, a_new, k: int, *, m: int, model_axis: str,
                 data_axes: Tuple[str, ...], plan):
    """The nested S/v delta, reduced straight onto the k-shards.

    Weights follow `rounds._delta_sv` (remove expired, add current;
    ``a_new == -1`` rows contribute nothing). Each model shard computes
    full-k partials over its row chunk, then psum_scatter over
    ``model_axis`` reduces the m chunks AND scatters the k-slices in one
    collective — each k-shard only ever materialises its own
    (k_local, d) slice of the delta — and psum over ``data_axes``
    completes the cross-shard sum.
    """
    seen = a_prev >= 0
    changed = seen & (a_new != a_prev)
    w_rm = jnp.where(changed, 1.0, 0.0).astype(jnp.float32)
    w_add = jnp.where((changed | ~seen) & (a_new >= 0), 1.0, 0.0) \
        .astype(jnp.float32)
    ap = jnp.clip(a_prev, 0, k - 1)
    an = jnp.clip(a_new, 0, k - 1)
    x_c, ap_c, an_c, w_rm_c, w_add_c = _chunk_rows(
        [x, ap, an, w_rm, w_add], m=m, model_axis=model_axis)
    S_rm, v_rm = ops.cluster_sum(x_c, ap_c, k, weights=w_rm_c, plan=plan)
    S_add, v_add = ops.cluster_sum(x_c, an_c, k, weights=w_add_c,
                                   plan=plan)
    dS = jax.lax.psum_scatter(S_add - S_rm, model_axis,
                              scatter_dimension=0, tiled=True)
    dv = jax.lax.psum_scatter(v_add - v_rm, model_axis,
                              scatter_dimension=0, tiled=True)
    if data_axes:
        dS, dv = jax.lax.psum((dS, dv), data_axes)
    return dS, dv


def _refresh_sse_xl(d_act, a_act, k: int, *, m: int, model_axis: str,
                    data_axes: Tuple[str, ...]):
    """sse(j) over active members for this shard's k-slice (exact)."""
    d_c, a_c = _chunk_rows([d_act, jnp.clip(a_act, 0, k - 1)], m=m,
                           model_axis=model_axis)
    sse_full = jax.ops.segment_sum(d_c * d_c, a_c, num_segments=k)
    sse = jax.lax.psum_scatter(sse_full, model_axis,
                               scatter_dimension=0, tiled=True)
    if data_axes:
        sse = jax.lax.psum(sse, data_axes)
    return sse


# --------------------------------------------------------------------------
# the nested XL round
# --------------------------------------------------------------------------

def xl_nested_round(X: jax.Array, state: KMeansState, *, b: int,
                    rho: float, bounds: str, m: int,
                    data_axes: Tuple[str, ...], model_axis: str,
                    capacity: Optional[int] = None, use_shalf: bool = True,
                    plan=None,
                    n_valid: Optional[jax.Array] = None
                    ) -> Tuple[KMeansState, RoundInfo]:
    """One gb/tb round over the per-shard prefix ``X[:b]``, k sharded.

    The centroid-sharded mirror of `rounds.nested_round`: ``state.stats``
    leaves hold this model shard's k-slice while ``state.points`` hold
    this data shard's rows (with GLOBAL assignment indices); ``b`` is the
    per-data-shard prefix and ``n_valid`` caps it against the shard's
    real rows exactly as in the mesh engine. Supports ``bounds`` "none"
    (gb: exhaustive sharded top-2 each round) and "hamerly2" (tb: exact-
    refresh upper bound + decayed second-nearest lower bound, with the
    threshold's s(j)/2 table built from all-gathered per-shard slices,
    and the same capacity compaction / overflow-retry contract as the
    local round), "elkan" (paper-faithful per-(i, j) bounds with
    the l matrix's k column sharded over the model axis —
    `_assign_elkan_xl`) and "exponion" (annular candidate pruning with
    the inter-centroid geometry built from ring-rotated centroid
    slices — `_assign_exponion_xl`). RoundInfo is replica-consistent on
    every device.
    """
    # trace accounting (see repro.util.tracecount): one count per jit
    # trace, keyed on the intended executable-cache statics (the plan is
    # constant per fit — a new static key, never a new bucket)
    tracecount.record("xl_nested_round", b=b, capacity=capacity, rho=rho,
                      bounds=bounds, plan=plan)
    k_local = state.stats.C.shape[0]
    k = k_local * m
    C_local = state.stats.C
    ax_m = jax.lax.axis_index(model_axis)
    k_offset = ax_m * k_local

    x = X[:b]
    a_prev = state.points.a[:b]
    valid = None if n_valid is None else jnp.arange(b) < n_valid

    def assign_fn(xs):
        return assign_top2_sharded(xs, C_local, model_axis=model_axis,
                                   k_offset=k_offset, plan=plan)

    # a pallas plan routes the dense shapes through the single-pass
    # fused kernel — but only at m == 1, where every model-axis
    # collective (psum_scatter, all_gather, pmax) is the identity and
    # the local k-slice IS the full centroid block. At m > 1 the
    # sharded per-op kernels below remain the dispatch target.
    fused = (plan is not None and plan.backend == "pallas" and m == 1
             and (bounds == "none"
                  or (bounds == "hamerly2"
                      and (capacity is None or capacity >= b))))
    fused_acc = None

    # the bound/compaction schedule itself lives ONLY in rounds.py; this
    # engine injects the four quantities that need model-axis
    # collectives, so the local and sharded paths cannot drift apart
    if fused:
        p_max = (jax.lax.pmax(jnp.max(state.stats.p), model_axis)
                 if bounds == "hamerly2" else None)
        a_new, d_new, lb2, n_rec, overflow, fused_acc = \
            rounds._fused_dense_round(x, state, a_prev, valid,
                                      bounds=bounds, use_shalf=use_shalf,
                                      plan=plan, p_max=p_max)
        l_new = None
    elif bounds == "none":
        a_new, d_new, lb2, n_rec, overflow, _ = rounds._assign_exhaustive(
            x, state, a_prev, valid, assign_top2_fn=assign_fn)
        l_new = None
    elif bounds == "hamerly2":
        p_max = jax.lax.pmax(jnp.max(state.stats.p), model_axis)
        d_a = _dist_to_assigned_sharded(x, C_local, a_prev, k_offset,
                                        model_axis)
        s_half = (_half_intercentroid_sharded(C_local, model_axis, m)
                  if use_shalf else None)
        a_new, d_new, lb2, n_rec, overflow, _ = rounds._assign_hamerly2(
            x, state, a_prev, valid, capacity=capacity,
            use_shalf=use_shalf, plan=plan,
            p_max=p_max, d_assigned=d_a, s_half=s_half,
            assign_top2_fn=assign_fn)
        l_new = None
    elif bounds == "elkan":
        a_new, d_new, lb2, n_rec, overflow, l_new = _assign_elkan_xl(
            x, state, a_prev, valid, k_local=k_local, k_offset=k_offset,
            model_axis=model_axis)
    elif bounds == "exponion":
        a_new, d_new, lb2, n_rec, overflow, l_new = _assign_exponion_xl(
            x, state, a_prev, valid, k_local=k_local, k_offset=k_offset,
            model_axis=model_axis, m=m, use_shalf=use_shalf)
    else:
        raise ValueError(f"unsupported bounds for the XL engine: "
                         f"{bounds!r} (use 'none', 'hamerly2', 'elkan' "
                         f"or 'exponion')")

    if valid is not None:
        a_new = jnp.where(valid, a_new, jnp.int32(-1))
        d_new = jnp.where(valid, d_new, 0.0)
        if lb2 is not None:
            lb2 = jnp.where(valid, lb2, 0.0)
        if l_new is not None:
            # pads keep a stable zero bound (their lanes are dead)
            l_new = jnp.where(valid[:, None], l_new, 0.0)

    if fused_acc is not None:
        # m == 1: the fused accumulators are already full-k; the model
        # psum_scatter would be the identity, only the data psum remains
        dS, dv, sse = fused_acc
        if data_axes:
            dS, dv, sse = jax.lax.psum((dS, dv, sse), data_axes)
    else:
        dS, dv = _delta_sv_xl(x, a_prev, a_new, k, m=m,
                              model_axis=model_axis, data_axes=data_axes,
                              plan=plan)
        sse = _refresh_sse_xl(d_new, a_new, k, m=m, model_axis=model_axis,
                              data_axes=data_axes)
    mse_num = jnp.sum(d_new * d_new)
    mse_den = (jnp.asarray(b, jnp.float32) if valid is None
               else jnp.sum(valid.astype(jnp.float32)))
    n_changed = jnp.sum(((a_prev >= 0) & (a_new != a_prev))
                        .astype(jnp.int32))
    n_active = (jnp.asarray(b, jnp.int32) if valid is None
                else jnp.sum(valid.astype(jnp.int32)))
    n_rec = n_rec.astype(jnp.int32)
    overflow = overflow.astype(jnp.int32)
    if data_axes:
        (mse_num, mse_den, n_changed, n_active, n_rec, overflow) = \
            jax.lax.psum((mse_num, mse_den, n_changed, n_active, n_rec,
                          overflow), data_axes)

    stats = dataclasses.replace(state.stats, S=state.stats.S + dS,
                                v=state.stats.v + dv, sse=sse)
    stats = centroid_update(stats)           # per-slice: C <- S/v, p

    # growth decision on the GLOBAL per-cluster stats (tiny vectors)
    v_all = jax.lax.all_gather(stats.v, model_axis, tiled=True)
    sse_all = jax.lax.all_gather(stats.sse, model_axis, tiled=True)
    p_all = jax.lax.all_gather(stats.p, model_axis, tiled=True)
    grow, r_med = controller.should_grow(sse_all, v_all, p_all, rho)

    points = dataclasses.replace(
        state.points,
        a=state.points.a.at[:b].set(a_new),
        d=state.points.d.at[:b].set(d_new))
    if lb2 is not None:
        points = dataclasses.replace(
            points, lb=points.lb.at[:b].set(lb2))
    elkan = state.elkan
    if l_new is not None:
        elkan = ElkanBounds(l=state.elkan.l.at[:b].set(l_new))

    info = RoundInfo(
        batch_mse=mse_num / jnp.maximum(mse_den, 1.0),
        n_changed=n_changed, n_recomputed=n_rec, n_active=n_active,
        overflow=overflow.astype(jnp.bool_), grow=grow, r_median=r_med,
        p_max=jax.lax.pmax(jnp.max(stats.p), model_axis))
    new_state = dataclasses.replace(state, stats=stats, points=points,
                                    elkan=elkan, round=state.round + 1)
    return new_state, info


# --------------------------------------------------------------------------
# shard_map factory + placement helpers
# --------------------------------------------------------------------------

def xl_state_specs(data_axes: Tuple[str, ...], model_axis: str,
                   *, elkan: bool = False):
    """PartitionSpec pytree of the XL engine's KMeansState layout.

    ``elkan``: include the per-(i, j) lower-bound matrix, rows sharded
    with the points and the k column sharded with the centroids.
    """
    row = P(data_axes)
    stats = ClusterStats(C=P(model_axis, None), S=P(model_axis, None),
                         v=P(model_axis), sse=P(model_axis),
                         p=P(model_axis))
    points = PointState(a=row, d=row, lb=row)
    el = ElkanBounds(l=P(data_axes, model_axis)) if elkan else None
    return KMeansState(stats=stats, points=points, elkan=el, round=P())


@functools.lru_cache(maxsize=None)
def make_xl_nested_round(mesh: Mesh, data_axes: Tuple[str, ...], *,
                         model_axis: str = "model", b_local: int,
                         rho: float, bounds: str = "hamerly2",
                         capacity: Optional[int] = None,
                         use_shalf: bool = True,
                         n_real: Optional[int] = None,
                         plan=None):
    """jit(shard_map(xl_nested_round)) for one (b_local, capacity) bucket.

    The centroid-sharded analogue of `distributed.make_sharded_round`:
    same static-key bucketing (the host loop compiles one executable per
    power-of-two (b, capacity) pair), same per-shard ``n_valid``
    derivation from ``n_real`` — plus the model-axis stat sharding.
    ``plan`` (the fit's resolved `KernelPlan`) joins the lru_cache key.
    """
    state_specs = xl_state_specs(data_axes, model_axis,
                                 elkan=(bounds == "elkan"))
    info_specs = RoundInfo(**{f.name: P() for f in
                              dataclasses.fields(RoundInfo)})
    sizes = tuple(int(mesh.shape[a]) for a in data_axes)
    n_shards = 1
    for s in sizes:
        n_shards *= s
    m = int(mesh.shape[model_axis])

    def fn(Xs, st):
        n_valid = per_shard_n_valid(data_axes, sizes, n_shards, n_real)
        return xl_nested_round(
            Xs, st, b=b_local, rho=rho, bounds=bounds, m=m,
            data_axes=data_axes, model_axis=model_axis, capacity=capacity,
            use_shalf=use_shalf, plan=plan, n_valid=n_valid)

    shardmapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(data_axes, None), state_specs),
        out_specs=(state_specs, info_specs), check_vma=False)
    return jax.jit(shardmapped)


def shard_state_xl(state: KMeansState, mesh: Mesh,
                   data_axes: Tuple[str, ...],
                   model_axis: str) -> KMeansState:
    """Place a host state onto the mesh with the XL engine's layout.

    The placement is derived from `xl_state_specs` — the ONE statement
    of the layout, shared with the shard_map in/out specs and the
    elastic-restore shardings (PartitionSpec is a pytree leaf, so the
    spec tree zips directly against the state).
    """
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        state, xl_state_specs(data_axes, model_axis))
