"""One-round update functions for every algorithm in the paper.

Each function is pure (state in -> state out) and jit-friendly with the
batch size ``b`` (and recompute ``capacity``) STATIC — the host driver
compiles one executable per power-of-two bucket (see driver.py). All are
exact: bound tests only ever *skip provably-unnecessary* work, so every
algorithm produces identical assignments to its exhaustive counterpart.

Algorithms (paper naming):
  * ``lloyd_round``         Lloyd's algorithm (full batch, fresh means).
  * ``mb_round``            Sculley's Mini-Batch (App. A.1 S/v form).
  * ``mbf_round``           mb-f: Mini-Batch with contamination removal.
  * ``nested_round``        gb-rho / tb-rho family on the nested prefix:
      bounds="none"       -> gb (exhaustive assignment each round)
      bounds="hamerly2"   -> tb, TPU-native two-bound + capacity compaction
      bounds="elkan"      -> tb, paper-faithful per-(i,j) lower bounds
      bounds="exponion"   -> tb, Hamerly test + annular candidate pruning
                             (Newling & Fleuret) for large k
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import controller
from repro.core.state import (ElkanBounds, ExponionGeom, KMeansState,
                              RoundInfo, build_exponion_geom,
                              centroid_update)
from repro.kernels import ops, ref
from repro.kernels.plan import KernelPlan
from repro.util import tracecount


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _euclid(d2: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def _dist_to_assigned(x: jax.Array, C: jax.Array, a: jax.Array) -> jax.Array:
    """Exact euclidean distance of each point to its assigned centroid."""
    Cg = C[jnp.clip(a, 0, C.shape[0] - 1)]
    return _euclid(jnp.sum((x.astype(jnp.float32) - Cg) ** 2, axis=1))


def _half_intercentroid(C: jax.Array) -> jax.Array:
    """Hamerly's s(j): half the distance to the nearest other centroid."""
    d2 = ref.pairwise_dist2(C, C)
    k = C.shape[0]
    d2 = d2.at[jnp.arange(k), jnp.arange(k)].set(jnp.inf)
    return 0.5 * _euclid(jnp.min(d2, axis=1))


def _prefix_count(mask: jax.Array, block: int = 512) -> jax.Array:
    """Inclusive running count of a boolean mask, exact in int32.

    Counts within blocks of ``block`` rows with one matmul against a
    triangular ones matrix (0/1 inputs, sums <= block: exact), then a
    cumsum over the b/block block totals. A plain cumsum over all b rows
    lowers on TPU to a program that takes ~11 s to compile at b=400k;
    this one takes ~2 s.
    """
    b = mask.shape[0]
    rows = -(-b // block)
    m = jnp.pad(mask.astype(jnp.float32), (0, rows * block - b))
    i = jnp.arange(block)
    tri = (i[:, None] <= i[None, :]).astype(jnp.float32)
    within = jnp.dot(m.reshape(rows, block), tri,
                     precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    totals = within[:, -1]
    return (within + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)[:b]


def _needs_first(needs: jax.Array, capacity: int) -> jax.Array:
    """Row indices of the first ``capacity`` rows when rows that need a
    rescan go first and the rest follow, each group in row order.

    Equal to ``jnp.argsort(jnp.where(needs, 0, 1), stable=True)
    [:capacity]``, without the sort: each row's place is a prefix count,
    and the rows placed below ``capacity`` are scattered into it. A
    stable sort over b rows takes ~30 s to compile on TPU at b=400k,
    once per (b, capacity) bucket.
    """
    b = needs.shape[0]
    before = _prefix_count(needs)            # needing rows up to row i
    n_need = before[-1]
    rows = jnp.arange(b, dtype=jnp.int32)
    place = jnp.where(needs, before - 1, n_need + rows - before)
    return jnp.zeros((capacity,), jnp.int32).at[place].set(rows,
                                                           mode="drop")


def _segment_scalar(vals: jax.Array, ids: jax.Array, k: int,
                    weights: jax.Array | None = None) -> jax.Array:
    if weights is not None:
        vals = vals * weights
    return jax.ops.segment_sum(vals, jnp.clip(ids, 0, k - 1), num_segments=k)


def _delta_sv(x: jax.Array, a_prev: jax.Array, a_new: jax.Array, k: int,
              plan: Optional[KernelPlan]):
    """The mb-f / nested S,v delta: remove expired, add current. Returns
    (dS, dv) so callers can psum the delta across data shards before
    applying it to the replicated stats. Rows with ``a_new == -1``
    (structural pads masked out of the active prefix) contribute
    nothing."""
    seen = a_prev >= 0
    changed = seen & (a_new != a_prev)
    w_rm = jnp.where(changed, 1.0, 0.0).astype(jnp.float32)
    w_add = jnp.where((changed | ~seen) & (a_new >= 0), 1.0, 0.0) \
        .astype(jnp.float32)
    a_new = jnp.clip(a_new, 0, k - 1)
    S_rm, v_rm = ops.cluster_sum(x, jnp.clip(a_prev, 0, k - 1), k,
                                 weights=w_rm, plan=plan)
    S_add, v_add = ops.cluster_sum(x, a_new, k, weights=w_add, plan=plan)
    return S_add - S_rm, v_add - v_rm


def _refresh_sse(d_act: jax.Array, a_act: jax.Array, k: int) -> jax.Array:
    """sse(j) = sum of d(i)^2 over active members (exact, no staleness)."""
    return _segment_scalar(d_act * d_act, a_act, k)


# --------------------------------------------------------------------------
# Lloyd
# --------------------------------------------------------------------------

def lloyd_round(X: jax.Array, state: KMeansState, *,
                plan: Optional[KernelPlan] = None
                ) -> Tuple[KMeansState, RoundInfo]:
    """Exact Lloyd iteration: full reassignment + fresh means."""
    k = state.stats.C.shape[0]
    n = X.shape[0]
    a_new, d1sq, _ = ops.assign_top2(X, state.stats.C, plan=plan)
    d = _euclid(d1sq)
    S, v = ops.cluster_sum(X, a_new, k, plan=plan)
    sse = _refresh_sse(d, a_new, k)
    stats = centroid_update(dataclasses.replace(
        state.stats, S=S, v=v, sse=sse))
    n_changed = jnp.sum((a_new != state.points.a).astype(jnp.int32))
    points = dataclasses.replace(state.points, a=a_new, d=d)
    info = RoundInfo(
        batch_mse=jnp.mean(d * d), n_changed=n_changed,
        n_recomputed=jnp.asarray(n, jnp.int32),
        n_active=jnp.asarray(n, jnp.int32),
        overflow=jnp.asarray(False), grow=jnp.asarray(False),
        r_median=jnp.asarray(jnp.inf, jnp.float32),
        p_max=jnp.max(stats.p))
    new_state = dataclasses.replace(state, stats=stats, points=points,
                                    round=state.round + 1)
    return new_state, info


# --------------------------------------------------------------------------
# Mini-Batch (Sculley) and mb-f
# --------------------------------------------------------------------------

def mb_round(X: jax.Array, idx: jax.Array, state: KMeansState, *,
             fixed: bool, plan: Optional[KernelPlan] = None
             ) -> Tuple[KMeansState, RoundInfo]:
    """One round of mb (Alg. 8 S/v form) or mb-f (Alg. 4, fixed=True).

    ``idx``: (b,) indices of this round's batch (driver cycles through a
    reshuffled permutation, per the paper's footnote 1 — no within-batch
    duplicates).
    """
    k = state.stats.C.shape[0]
    b = idx.shape[0]
    x = X[idx]
    a_new, d1sq, _ = ops.assign_top2(x, state.stats.C, plan=plan)
    d = _euclid(d1sq)

    if fixed:
        a_prev = state.points.a[idx]
        dS, dv = _delta_sv(x, a_prev, a_new, k, plan)
        stats = dataclasses.replace(state.stats, S=state.stats.S + dS,
                                    v=state.stats.v + dv)
        n_changed = jnp.sum(((a_prev >= 0) & (a_new != a_prev))
                            .astype(jnp.int32))
    else:
        # plain mb never removes: every (re)assignment accumulates forever
        S_add, v_add = ops.cluster_sum(x, a_new, k, plan=plan)
        stats = dataclasses.replace(state.stats, S=state.stats.S + S_add,
                                    v=state.stats.v + v_add)
        n_changed = jnp.asarray(b, jnp.int32)

    stats = centroid_update(stats)
    points = dataclasses.replace(
        state.points,
        a=state.points.a.at[idx].set(a_new),
        d=state.points.d.at[idx].set(d))
    info = RoundInfo(
        batch_mse=jnp.mean(d * d), n_changed=n_changed,
        n_recomputed=jnp.asarray(b, jnp.int32),
        n_active=jnp.asarray(b, jnp.int32),
        overflow=jnp.asarray(False), grow=jnp.asarray(False),
        r_median=jnp.asarray(jnp.inf, jnp.float32),
        p_max=jnp.max(stats.p))
    new_state = dataclasses.replace(state, stats=stats, points=points,
                                    round=state.round + 1)
    return new_state, info


def mbf_round(X, idx, state, *, plan=None):
    return mb_round(X, idx, state, fixed=True, plan=plan)


# --------------------------------------------------------------------------
# Nested (grow-batch) rounds: gb-rho / tb-rho
# --------------------------------------------------------------------------

def _assign_exhaustive(x, state, a_prev, valid, *, plan=None,
                       assign_top2_fn=None):
    """bounds='none': full top-2 for every active point.

    ``assign_top2_fn`` lets the centroid-sharded engine inject its
    collective top-2 (`distributed_xl`); the schedule stays identical.
    """
    if assign_top2_fn is None:
        a_new, d1sq, d2sq = ops.assign_top2(x, state.stats.C, plan=plan)
    else:
        a_new, d1sq, d2sq = assign_top2_fn(x)
    n_rec = (jnp.asarray(x.shape[0], jnp.int32) if valid is None
             else jnp.sum(valid.astype(jnp.int32)))
    return (a_new, _euclid(d1sq), _euclid(d2sq), n_rec,
            jnp.asarray(False), None)


def _hamerly_settled(x, state, a_prev, valid, *, use_shalf: bool,
                     p_max=None, d_assigned=None, s_half=None):
    """The Hamerly bound DECISIONS for one round's active slice.

    Factored out of `_assign_hamerly2` so the fused pallas round can
    reuse the decisions verbatim: whatever backend executes the
    assignment, the settled mask — and therefore the bound/compaction
    schedule — comes from this one function.

    Returns (settled, lb_dec, d_a, n_need).
    """
    C = state.stats.C
    b = x.shape[0]
    seen = a_prev >= 0
    if p_max is None:
        p_max = jnp.max(state.stats.p)
    lb_dec = state.points.lb[:b] - p_max
    d_a = (_dist_to_assigned(x, C, a_prev) if d_assigned is None
           else d_assigned)
    thresh = lb_dec
    if use_shalf:
        if s_half is None:
            s_half = _half_intercentroid(C)
        thresh = jnp.maximum(lb_dec, s_half[jnp.clip(a_prev, 0, None)])
    settled = seen & (d_a <= thresh)
    if valid is not None:
        # masked structural pads never need recompute; their outputs are
        # forced back to the never-assigned sentinel by the caller
        settled = settled | ~valid
    n_need = jnp.sum((~settled).astype(jnp.int32))
    return settled, lb_dec, d_a, n_need


def _fused_dense_round(x, state, a_prev, valid, *, bounds: str,
                       use_shalf: bool, plan: KernelPlan,
                       p_max=None, d_assigned=None, s_half=None):
    """Route the dense assignment through `ops.fused_nested_round`.

    One pass over x replaces the assign / delta-S/v / sse triple-read
    when the plan picked pallas. Only the DENSE shapes go here (gb, or
    tb with capacity covering the batch); the compacted tb path keeps
    the separate kernels because its gather/scatter breaks the
    single-sweep structure. Returns the `_assign_*` 6-tuple plus the
    fused (dS, dv, sse) accumulators in the last slot.
    """
    b = x.shape[0]
    if bounds == "hamerly2":
        settled, lb_dec, d_a, n_rec = _hamerly_settled(
            x, state, a_prev, valid, use_shalf=use_shalf, p_max=p_max,
            d_assigned=d_assigned, s_half=s_half)
    else:                               # bounds == "none"
        settled = jnp.zeros((b,), jnp.bool_)
        lb_dec = jnp.zeros((b,), jnp.float32)
        d_a = jnp.zeros((b,), jnp.float32)
        n_rec = (jnp.asarray(b, jnp.int32) if valid is None
                 else jnp.sum(valid.astype(jnp.int32)))
    vmask = jnp.ones((b,), jnp.bool_) if valid is None else valid
    a_new, d_new, lb_new, dS, dv, sse = ops.fused_nested_round(
        x, state.stats.C, a_prev, settled, d_a, lb_dec, vmask, plan=plan)
    return (a_new, d_new, lb_new, n_rec.astype(jnp.int32),
            jnp.asarray(False), (dS, dv, sse))


def _assign_hamerly2(x, state, a_prev, valid, *, capacity: Optional[int],
                     use_shalf: bool, plan=None,
                     p_max=None, d_assigned=None, s_half=None,
                     assign_top2_fn=None):
    """TPU-native bounding: exact-refresh upper + decayed 2nd-nearest lower.

    Per round (active slice, all vectorised):
      1. lb' = lb - max_j p(j)                       (bound decay, eq. 4)
      2. d_a = ||x - C(a)|| exact for every point    (O(b d), negligible)
      3. settled iff d_a <= max(lb', s_half(a))      (Hamerly tests)
      4. the unsettled are COMPACTED into a ``capacity``-sized buffer and
         only that buffer hits the fused top-2 kernel — tile-level work
         elimination (the TPU adaptation of Elkan's per-scalar skip).
    Settled points keep their assignment with an EXACT distance (step 2),
    so sse / sigma_C stay exact. If more than ``capacity`` points need
    recompute the round reports overflow=True and the driver retries the
    same input state with a larger bucket — exactness is never sacrificed.
    ``capacity=None`` recomputes everything (used for b == capacity).
    A compacted round hands back its buffer ``(x_cap, idx_cap)`` in the
    last slot (None otherwise): only those rows can change cluster, so
    the delta S/v need not read the rest of the prefix.

    The optional ``p_max`` / ``d_assigned`` / ``s_half`` /
    ``assign_top2_fn`` overrides exist for the centroid-sharded engine
    (`core.distributed_xl`), which precomputes these four quantities
    with model-axis collectives — the bound/compaction schedule itself
    lives ONLY here, so the engines cannot drift apart.
    """
    C = state.stats.C
    b = x.shape[0]
    if assign_top2_fn is None:
        def assign_top2_fn(xs):
            return ops.assign_top2(xs, C, plan=plan)
    settled, lb_dec, d_a, n_need = _hamerly_settled(
        x, state, a_prev, valid, use_shalf=use_shalf, p_max=p_max,
        d_assigned=d_assigned, s_half=s_half)
    needs = ~settled

    if capacity is None or capacity >= b:
        a_full, d1sq, d2sq = assign_top2_fn(x)
        d1, d2 = _euclid(d1sq), _euclid(d2sq)
        a_new = jnp.where(settled, a_prev, a_full)
        d_new = jnp.where(settled, d_a, d1)
        lb_new = jnp.where(settled, lb_dec, d2)
        return a_new, d_new, lb_new, n_need, jnp.asarray(False), None

    # compact-and-batch: unsettled points first, each group in row order
    idx_cap = _needs_first(needs, capacity)
    x_cap = x[idx_cap]
    a_cap, d1sq, d2sq = assign_top2_fn(x_cap)
    d1, d2 = _euclid(d1sq), _euclid(d2sq)

    # settled points carry the decayed bound + exact distance ...
    a_new = jnp.where(settled, a_prev, a_prev)   # placeholder, fixed below
    d_new = jnp.where(settled, d_a, state.points.d[:b])
    lb_new = jnp.where(settled, lb_dec, state.points.lb[:b])
    # ... and the recomputed buffer is scattered back (exact for every
    # entry, including any settled points that padded the buffer).
    a_new = a_new.at[idx_cap].set(a_cap)
    d_new = d_new.at[idx_cap].set(d1)
    lb_new = lb_new.at[idx_cap].set(d2)
    overflow = n_need > capacity
    return (a_new, d_new, lb_new, jnp.minimum(n_need, capacity), overflow,
            (x_cap, idx_cap))


def _assign_elkan(x, state, a_prev, valid, *, b: int):
    """Paper-faithful tb bounds (supp. Alg. 9/11): l(i,j), one per pair.

    Vectorised semantics (see DESIGN.md): all bound-passing distances are
    computed at once instead of serially; the final assignment is
    identical, and ``n_recomputed`` counts the pair-distance computations
    a serial implementation would have had to do (upper bound thereof).

    ``valid`` masks structural pad rows (mesh engines, N % n_shards
    != 0): their compute mask is forced off, so they never touch a
    distance, and the caller resets their outputs to the sentinel.
    """
    C = state.stats.C
    k = C.shape[0]
    seen = a_prev >= 0
    l_dec = state.elkan.l[:b] - state.stats.p[None, :]      # eq. (4)
    d_a = _dist_to_assigned(x, C, a_prev)

    d_all = _euclid(ref.pairwise_dist2(x, C))               # (b, k)
    cols = jnp.arange(k)[None, :]
    own = cols == a_prev[:, None]
    compute = (l_dec < d_a[:, None]) & ~own                 # bound test
    compute = compute | ~seen[:, None]                      # new pts: all k
    if valid is not None:
        compute = compute & valid[:, None]

    l_new = jnp.where(compute, d_all, l_dec)
    cand = jnp.where(compute, d_all, jnp.inf)
    cand = jnp.where(own & seen[:, None], d_a[:, None], cand)
    a_new = jnp.argmin(cand, axis=1).astype(jnp.int32)
    d_new = jnp.min(cand, axis=1)
    # + the d_a's (pads are never seen, so they add nothing here)
    n_comp = jnp.sum(compute.astype(jnp.int32)) \
        + jnp.sum(seen.astype(jnp.int32))
    return a_new, d_new, None, n_comp, jnp.asarray(False), l_new


def _assign_exponion(x, state, a_prev, valid, *, use_shalf: bool,
                     geom: Optional[ExponionGeom] = None,
                     p_max=None, d_assigned=None):
    """Annular candidate pruning (Newling & Fleuret's exponion).

    Reuses the Hamerly settled test verbatim (`_hamerly_settled`, with
    ``s/2`` read off the geometry table instead of recomputed); a point
    that FAILS the test scans only the centroids inside the ball of
    radius R = 2*d(x, c_a) + s(a) around its anchor — never the full k.

    Exactness: any centroid c_j outside the ball has
    d(x, c_j) >= d(c_a, c_j) - d(x, c_a) > R - u = u + s(a), while the
    anchor (distance u) and the anchor's nearest neighbour (distance
    <= u + s(a) by the triangle inequality) are ALWAYS candidates — so
    the candidate argmin is the true argmin (every centroid tied at the
    minimum satisfies d(c_a, c_j) <= 2u <= R, preserving the
    lowest-index tie-break of ``bounds="none"``) and the candidate
    second-minimum is the exact second-nearest distance, making the
    stored ``lb`` as tight as an exhaustive scan's. Boundary ties
    (d(c_a, c_j) == R exactly) are INCLUDED via a ``<=`` ring count.

    The candidate mask is the EXACT annulus (``rank < m_exact``) — the
    same set the centroid-sharded variant tests per slice, so the
    ``n_recomputed`` accounting is identical across backends. All
    shapes depend only on (b, k) — the ring count is a traced VALUE —
    so the retrace auditor's one-trace-per-(b, capacity) bucket
    contract is untouched. (The paper's log2-bucketed ring layout is a
    cache-locality play for scalar CPUs; on a vectorised backend the
    mask is free and padding the ring only inflates the honest count.)

    ``n_recomputed`` counts actual pair-distance evaluations (annulus
    scans + the per-seen-point d_a refresh), the elkan convention — NOT
    hamerly2's k-scan unit.

    The optional ``geom`` / ``p_max`` / ``d_assigned`` overrides exist
    for the centroid-sharded engine (`core.distributed_xl`), which
    builds the geometry from all-gathered centroid slices; the annulus
    schedule itself lives ONLY here.
    """
    C = state.stats.C
    k = C.shape[0]
    b = x.shape[0]
    if geom is None:
        geom = build_exponion_geom(C)
    seen = a_prev >= 0
    settled, lb_dec, d_a, _n_need = _hamerly_settled(
        x, state, a_prev, valid, use_shalf=use_shalf, p_max=p_max,
        d_assigned=d_assigned, s_half=0.5 * geom.s)
    needs = ~settled

    anchor = jnp.clip(a_prev, 0, k - 1)
    R = 2.0 * d_a + geom.s[anchor]
    rows = geom.dist[anchor]                                # (b, k) sorted
    m_exact = jnp.sum((rows <= R[:, None]).astype(jnp.int32), axis=1)
    ring = geom.rank[anchor] < m_exact[:, None]             # (b, k)
    scan = needs[:, None] & (ring | ~seen[:, None])         # new pts: all k
    if valid is not None:
        scan = scan & valid[:, None]

    # candidate top-2 in SQUARED space (the exact values and tie-break
    # order of `ops.assign_top2` on the full row), sqrt at the boundary
    d2_all = ref.pairwise_dist2(x, C)                       # (b, k)
    cand = jnp.where(scan, d2_all, jnp.inf)
    a_f = jnp.argmin(cand, axis=1).astype(jnp.int32)
    d1sq = jnp.take_along_axis(cand, a_f[:, None], axis=1)[:, 0]
    rest = jnp.where(jnp.arange(k)[None, :] == a_f[:, None], jnp.inf, cand)
    d1, d2 = _euclid(d1sq), _euclid(jnp.min(rest, axis=1))

    a_new = jnp.where(settled, a_prev, a_f)
    d_new = jnp.where(settled, d_a, d1)
    lb_new = jnp.where(settled, lb_dec, d2)
    # pair-distance accounting (elkan convention): every scanned
    # (point, centroid) pair + the d_a refresh of every seen point
    # (pads are never seen, so they add nothing)
    n_comp = jnp.sum(scan.astype(jnp.int32)) \
        + jnp.sum(seen.astype(jnp.int32))
    return a_new, d_new, lb_new, n_comp, jnp.asarray(False), None


def nested_round(X: jax.Array, state: KMeansState, *, b: int,
                 rho: float, bounds: str = "hamerly2",
                 capacity: Optional[int] = None, use_shalf: bool = True,
                 plan: Optional[KernelPlan] = None,
                 data_axes: Tuple[str, ...] = (),
                 n_valid: Optional[jax.Array] = None
                 ) -> Tuple[KMeansState, RoundInfo]:
    """One gb/tb round over the nested prefix ``X[:b]`` (b STATIC).

    Covers Alg. 7 (gb-rho), Alg. 9 (tb-rho) and their rho=inf degenerate
    forms (Alg. 10/11): previously-seen points are reassigned with delta
    S/v corrections, unseen points ``a(i) == -1`` enter the batch, the
    centroids move to S/v, and the controller votes on doubling b.

    ``data_axes``: when called inside shard_map with points sharded over
    these mesh axes, X/state.points are per-shard slices (b is the LOCAL
    prefix; the global batch is the union of shard prefixes), the S/v/sse
    deltas are psum-reduced so the replicated stats — and therefore the
    growth decision — stay bit-identical on every shard.

    ``n_valid``: optional per-call scalar capping the REAL rows of this
    slice. Rows at positions >= n_valid are structural pads: they are
    held out of the assignment (``a == -1``), contribute nothing to
    S/v/sse/mse, and are excluded from n_active/n_changed. This is how a
    shard whose real-row count is not a multiple of the shard count caps
    ``b`` against its own real rows while b stays a shared static.

    ``plan``: the fit's resolved `KernelPlan` (hashable, constant per
    fit — engines pass it as a jit STATIC). A pallas plan routes the
    dense gb/tb shapes through the single-pass fused kernel.
    """
    # trace accounting: this body runs once per jit trace; the statics
    # here ARE the intended executable-cache key (repro.analysis.retrace
    # asserts the trace count never exceeds the pow2 bucket count — the
    # plan is constant for a fit, so it widens no bucket)
    tracecount.record("nested_round", b=b, capacity=capacity, rho=rho,
                      bounds=bounds, plan=plan)
    k = state.stats.C.shape[0]
    x = X[:b]
    a_prev = state.points.a[:b]
    valid = None if n_valid is None else jnp.arange(b) < n_valid

    fused = (plan is not None and plan.backend == "pallas"
             and (bounds == "none"
                  or (bounds == "hamerly2"
                      and (capacity is None or capacity >= b))))
    fused_acc = None
    buffer = None
    if fused:
        a_new, d_new, lb2, n_rec, overflow, fused_acc = \
            _fused_dense_round(x, state, a_prev, valid, bounds=bounds,
                               use_shalf=use_shalf, plan=plan)
        l_new = None
    elif bounds == "none":
        a_new, d_new, lb2, n_rec, overflow, l_new = _assign_exhaustive(
            x, state, a_prev, valid, plan=plan)
    elif bounds == "hamerly2":
        a_new, d_new, lb2, n_rec, overflow, buffer = _assign_hamerly2(
            x, state, a_prev, valid, capacity=capacity,
            use_shalf=use_shalf, plan=plan)
        l_new = None
    elif bounds == "elkan":
        a_new, d_new, lb2, n_rec, overflow, l_new = \
            _assign_elkan(x, state, a_prev, valid, b=b)
    elif bounds == "exponion":
        a_new, d_new, lb2, n_rec, overflow, l_new = _assign_exponion(
            x, state, a_prev, valid, use_shalf=use_shalf)
    else:
        raise ValueError(f"unknown bounds {bounds!r}")

    if valid is not None:
        # idempotent on the fused path (the kernel already masked)
        a_new = jnp.where(valid, a_new, jnp.int32(-1))
        d_new = jnp.where(valid, d_new, 0.0)
        if lb2 is not None:
            lb2 = jnp.where(valid, lb2, 0.0)
        if l_new is not None:
            # pads keep a stable zero bound (their lanes are dead)
            l_new = jnp.where(valid[:, None], l_new, 0.0)

    if fused_acc is not None:
        dS, dv, sse = fused_acc
    else:
        if buffer is not None:
            # every row outside hamerly2's compacted buffer keeps
            # a_new == a_prev and weighs 0 in both passes (idx_cap holds
            # unique rows; a masked pad in it has a_new == -1)
            x_cap, idx_cap = buffer
            dS, dv = _delta_sv(x_cap, a_prev[idx_cap], a_new[idx_cap], k,
                               plan)
        else:
            dS, dv = _delta_sv(x, a_prev, a_new, k, plan)
        sse = _refresh_sse(d_new, a_new, k)
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
        (dS, dv, sse, mse_num, mse_den, n_changed, n_active, n_rec,
         overflow) = jax.lax.psum(
            (dS, dv, sse, mse_num, mse_den, n_changed, n_active, n_rec,
             overflow), data_axes)

    stats = dataclasses.replace(state.stats, S=state.stats.S + dS,
                                v=state.stats.v + dv, sse=sse)
    stats = centroid_update(stats)

    grow, r_med = controller.should_grow(stats.sse, stats.v, stats.p, rho)

    points = dataclasses.replace(
        state.points,
        a=state.points.a.at[:b].set(a_new),
        d=state.points.d.at[:b].set(d_new))
    if lb2 is not None:
        points = dataclasses.replace(points,
                                     lb=points.lb.at[:b].set(lb2))
    elkan = state.elkan
    if l_new is not None:
        elkan = ElkanBounds(l=state.elkan.l.at[:b].set(l_new))

    info = RoundInfo(
        batch_mse=mse_num / jnp.maximum(mse_den, 1.0), n_changed=n_changed,
        n_recomputed=n_rec, n_active=n_active,
        overflow=overflow.astype(jnp.bool_), grow=grow, r_median=r_med,
        p_max=jnp.max(stats.p))
    new_state = dataclasses.replace(state, stats=stats, points=points,
                                    elkan=elkan, round=state.round + 1)
    return new_state, info
