"""Fused nested k-means round: assign + Hamerly keep-select + delta-S/v
+ sse in ONE pass over X.

The paper's assignment step followed by the S/v/sse accumulation reads X
twice when expressed as separate ops. On TPU the whole round is a single
Pallas kernel:

  * the full centroid block C (kp, d) stays VMEM-resident,
  * grid over point tiles (sequential): each (bn, d) X tile is read from
    HBM exactly once; the MXU computes the (kp, bn) distance block; the
    VPU folds top-2 over the centroid axis and accumulates
        S += coeff·X        (MXU)
        v += Σ coeff, sse += Σ d²
    into revisited (kp, d) / (kp, 1) output blocks that never leave VMEM.

Layout: centroids on sublanes, rows on lanes. The distance block is
(kp, bn), so every per-row quantity — the assignment, distances,
bounds and masks — is a lane-dense (1, bn) row, and the per-row vectors
travel in HBM as (1, n) arrays. Rank-1 (bn,) blocks do not compile
(XLA and Mosaic disagree on their tiling), and a (bn, 1) column would
pad every row to 128 lanes.

HBM traffic per round = |X| + |C| + |outputs| — the optimal single pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plan import check_tile, vmem_limit_bytes

HIGHEST = jax.lax.Precision.HIGHEST
#: contract the last dim of both operands: (m, d) x (n, d) -> (m, n)
NT = (((1,), (1,)), ((), ()))
#: plain matmul: (m, n) x (n, d) -> (m, d)
NN = (((1,), (0,)), ((), ()))


def row_norms(x: jax.Array) -> jax.Array:
    """Squared norms of the rows of ``x`` (bn, d) as a lane-dense (1, bn)
    row: a contraction against a ones row keeps rows on lanes, where a
    sum over d would leave them on sublanes."""
    ones = jnp.ones((1, x.shape[1]), jnp.float32)
    return jax.lax.dot_general(ones, x * x, NT, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def dist2_block(x: jax.Array, c: jax.Array, cn: jax.Array) -> jax.Array:
    """(kp, bn) squared distances between centroids ``c`` (kp, d) with
    column norms ``cn`` (kp, 1) and rows ``x`` (bn, d): the `ref`
    expression ``max(|x|² - 2x·c + |c|², 0)``, so labels match it."""
    dot = jax.lax.dot_general(c, x, NT, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
    return jnp.maximum(row_norms(x) - 2.0 * dot + cn, 0.0)


def top2(d2m: jax.Array):
    """(idx, min, 2nd-min) over the centroid axis 0 of ``d2m`` (kp, bn),
    each (1, bn). Ties go to the lowest index, as `jnp.argmin`."""
    rows = jax.lax.broadcasted_iota(jnp.int32, d2m.shape, 0)
    b1 = jnp.min(d2m, axis=0, keepdims=True)
    idx = jnp.min(jnp.where(d2m == b1, rows, d2m.shape[0]), axis=0,
                  keepdims=True)
    b2 = jnp.min(jnp.where(rows == idx, jnp.inf, d2m), axis=0,
                 keepdims=True)
    return idx, b1, b2, rows


def to_row(v: jax.Array, n_pad: int, fill) -> jax.Array:
    """(n,) vector -> lane-dense (1, n + n_pad) row, padded with ``fill``."""
    if n_pad:
        v = jnp.pad(v, (0, n_pad), constant_values=fill)
    return v.reshape(1, -1)


def _nested_kernel(x_ref, c_ref, cn_ref, ap_ref, keep_ref, dk_ref,
                   lbk_ref, vm_ref, a_ref, d_ref, lb_ref, s_ref, v_ref,
                   sse_ref, *, k: int):
    """One tile of the fused NESTED round (see `fused_nested_round_pallas`).

    The Hamerly bound DECISIONS arrive pre-made as the ``keep`` mask —
    the kernel only executes them, so the growth/bound schedule is
    identical between backends by construction. For kept rows the
    retained distance/bound (dk/lbk) pass straight through; everyone
    still pays the distance matmul because the dense nested path
    refreshes the second-closest bound for all rows each round.
    """
    n_idx = pl.program_id(0)

    x = x_ref[...].astype(jnp.float32)            # (bn, d)
    ap = ap_ref[...]                              # (1, bn) prev assignment
    keep = keep_ref[...] != 0                     # settled: keep a_prev
    vm = vm_ref[...] != 0                         # valid (un-padded) rows

    # +inf norms on pad centroids: never the argmin
    d2m = dist2_block(x, c_ref[...], cn_ref[...])   # (kp, bn)
    af, b1, b2, rows = top2(d2m)
    d1 = jnp.sqrt(b1)
    d2 = jnp.sqrt(b2)

    a_new = jnp.where(vm, jnp.where(keep, ap, af), -1)
    d_new = jnp.where(vm, jnp.where(keep, dk_ref[...], d1), 0.0)
    lb_new = jnp.where(vm, jnp.where(keep, lbk_ref[...], d2), 0.0)
    a_ref[...] = a_new
    d_ref[...] = d_new
    lb_ref[...] = lb_new

    # delta-S/v for already-seen points (rounds._delta_sv semantics),
    # folded into ONE matmul via a signed coefficient matrix: +1 at the
    # new cluster for joins, -1 at the old cluster for leaves. Masked
    # rows (a_new == -1) and grid pads carry zero coefficients.
    seen = ap >= 0
    changed = seen & (a_new != ap)
    w_rm = jnp.where(changed, 1.0, 0.0)
    w_add = jnp.where((changed | ~seen) & (a_new >= 0), 1.0, 0.0)
    add_oh = (rows == jnp.clip(a_new, 0, k - 1)).astype(jnp.float32)
    rm_oh = (rows == jnp.clip(ap, 0, k - 1)).astype(jnp.float32)
    coeff = w_add * add_oh - w_rm * rm_oh                     # (kp, bn)
    s_part = jax.lax.dot_general(coeff, x, NN, precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    v_part = jnp.sum(coeff, axis=1, keepdims=True)            # (kp, 1)
    sse_part = jnp.sum(add_oh * (d_new * d_new), axis=1, keepdims=True)

    @pl.when(n_idx == 0)
    def _init():
        s_ref[...] = s_part
        v_ref[...] = v_part
        sse_ref[...] = sse_part

    @pl.when(n_idx != 0)
    def _acc():
        s_ref[...] += s_part
        v_ref[...] += v_part
        sse_ref[...] += sse_part


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fused_nested_round_pallas(x: jax.Array, c: jax.Array,
                              a_prev: jax.Array, settled: jax.Array,
                              d_keep: jax.Array, lb_keep: jax.Array,
                              valid: jax.Array, *, bn: int = 256,
                              interpret: bool = False):
    """Fused nested-prefix round: assign + Hamerly keep-select +
    delta-S/v + sse in ONE pass over x.

    Inputs beyond (x, c): the previous assignment, the pre-computed
    ``settled`` mask (rows whose Hamerly s/2 / lower bound proved the
    assignment cannot change), the retained EUCLIDEAN distance and
    decayed lower bound for settled rows, and the valid-row mask.

    Returns (a_new, d_new, lb_new, dS, dv, sse): post-mask assignments
    (-1 on invalid rows), euclidean distance to the assigned centroid,
    the refreshed second-closest lower bound, the signed delta cluster
    sums/counts for seen points, and per-cluster sse of active members.
    ``bn`` must be a TPU row tile (`plan.check_tile`).
    """
    check_tile("fused_nested_round_pallas", bn=bn)
    n, d = x.shape
    k = c.shape[0]
    kp = k + (-k % 128)
    cf = c.astype(jnp.float32)
    cn = jnp.sum(cf ** 2, axis=1)
    if kp != k:
        cf = jnp.pad(cf, ((0, kp - k), (0, 0)))
        cn = jnp.pad(cn, (0, kp - k), constant_values=jnp.inf)
    n_pad = -n % bn
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    np_ = x.shape[0]
    # pad rows: a_prev=-1 (unseen) + valid=0 ⇒ every coefficient and
    # sse term is zero; outputs are sliced off below.
    rows_in = (to_row(a_prev, n_pad, -1),
               to_row(settled.astype(jnp.int32), n_pad, 1),
               to_row(d_keep, n_pad, 0.0), to_row(lb_keep, n_pad, 0.0),
               to_row(valid.astype(jnp.int32), n_pad, 0))

    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    full = pl.BlockSpec((kp, d), lambda i: (0, 0))
    col = pl.BlockSpec((kp, 1), lambda i: (0, 0))
    vmem = vmem_limit_bytes(
        "fused_nested_round_pallas",
        blocks=[(bn, d), (kp, d), (kp, d), (kp, 1), (kp, 1), (kp, 1)]
        + [(1, bn)] * 8,
        temps=[(kp, bn)] * 8 + [(kp, d)])
    a, dn, lb, S, v, sse = pl.pallas_call(
        functools.partial(_nested_kernel, k=k),
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)), full, col]
        + [row] * 5,
        out_specs=[row, row, row, full, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x, cf, cn[:, None], *rows_in)
    return (a[0, :n], dn[0, :n], lb[0, :n], S[:k], v[:k, 0], sse[:k, 0])


def fused_nested_round_ref(x: jax.Array, c: jax.Array, a_prev: jax.Array,
                           settled: jax.Array, d_keep: jax.Array,
                           lb_keep: jax.Array, valid: jax.Array):
    """Pure-jnp oracle mirroring the ref round path op for op."""
    from repro.kernels import ref

    k = c.shape[0]
    af, d1sq, d2sq = ref.assign_top2_ref(x, c)
    d1 = jnp.sqrt(jnp.maximum(d1sq, 0.0))
    d2 = jnp.sqrt(jnp.maximum(d2sq, 0.0))
    settled = settled.astype(bool)
    valid = valid.astype(bool)
    a_new = jnp.where(valid, jnp.where(settled, a_prev, af),
                      -1).astype(jnp.int32)
    d_new = jnp.where(valid, jnp.where(settled, d_keep, d1), 0.0)
    lb_new = jnp.where(valid, jnp.where(settled, lb_keep, d2), 0.0)
    seen = a_prev >= 0
    changed = seen & (a_new != a_prev)
    w_rm = jnp.where(changed, 1.0, 0.0).astype(jnp.float32)
    w_add = jnp.where((changed | ~seen) & (a_new >= 0),
                      1.0, 0.0).astype(jnp.float32)
    S_rm, v_rm = ref.cluster_sum_ref(x, jnp.clip(a_prev, 0, k - 1), k,
                                     weights=w_rm)
    S_add, v_add = ref.cluster_sum_ref(x, jnp.clip(a_new, 0, k - 1), k,
                                       weights=w_add)
    sse = jax.ops.segment_sum(d_new * d_new, jnp.clip(a_new, 0, k - 1),
                              num_segments=k)
    return (a_new, d_new, lb_new, S_add - S_rm, v_add - v_rm, sse)


def fused_round_pallas(x: jax.Array, c: jax.Array, *, bn: int = 256,
                       interpret: bool = False):
    """One fused assignment+accumulation pass over fresh rows.

    x: (n, d), c: (k, d). Returns (a, d1_sq, d2_sq, S, v, sse) where
    S/v/sse are the per-cluster sums/counts/sse of THIS pass: the nested
    kernel with every row unseen and valid, so all rows join.
    """
    n = x.shape[0]
    a, d1, d2, S, v, sse = fused_nested_round_pallas(
        x, c, jnp.full((n,), -1, jnp.int32), jnp.zeros((n,), jnp.bool_),
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.bool_), bn=bn, interpret=interpret)
    return a, d1 * d1, d2 * d2, S, v, sse


def fused_round_ref(x: jax.Array, c: jax.Array):
    """Pure-jnp oracle for the fused round."""
    from repro.kernels import ref

    d2m = ref.pairwise_dist2(x, c)
    a = jnp.argmin(d2m, axis=1).astype(jnp.int32)
    d1 = jnp.min(d2m, axis=1)
    k = c.shape[0]
    cols = jnp.arange(k)[None, :]
    d2nd = jnp.min(jnp.where(cols == a[:, None], jnp.inf, d2m), axis=1)
    S, v = ref.cluster_sum_ref(x, a, k)
    sse = jax.ops.segment_sum(d1, a, num_segments=k)
    return a, d1, d2nd, S, v, sse
