"""Per-cluster sum/count as a one-hot MXU matmul Pallas kernel.

TPU scatter-adds serialise; for small-to-moderate k the MXU-friendly form
``S = onehot(a).T @ x`` is the idiomatic replacement for segment_sum. Used
for the bulk cluster-sum over newly-entered points in nested rounds.

Grid: (d_blocks, n_blocks) with n sequential so the (k, bd) output block
accumulates across point tiles; counts are folded on the first d block
only. Labels and weights arrive as lane-dense (1, bn) blocks, and the
one-hot is built transposed, (k, bn), so S is a plain (k, bn) x (bn, bd)
matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_round import HIGHEST, NN, to_row
from repro.kernels.plan import check_tile, vmem_limit_bytes


def _cluster_sum_kernel(x_ref, a_ref, w_ref, s_ref, v_ref, *, kp: int):
    d_idx = pl.program_id(0)
    n_idx = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)             # (bn, bd)
    a = a_ref[...]                                 # (1, bn)
    w = w_ref[...].astype(jnp.float32)             # (1, bn) weights

    rows = jax.lax.broadcasted_iota(jnp.int32, (kp, x.shape[0]), 0)
    onehot = jnp.where(rows == a, w, 0.0)          # (kp, bn)

    part = jax.lax.dot_general(onehot, x, NN, precision=HIGHEST,
                               preferred_element_type=jnp.float32)

    @pl.when(n_idx == 0)
    def _init():
        s_ref[...] = part

    @pl.when(n_idx != 0)
    def _acc():
        s_ref[...] += part

    @pl.when(d_idx == 0)
    def _counts():
        vpart = jnp.sum(onehot, axis=1, keepdims=True)   # (kp, 1)

        @pl.when(n_idx == 0)
        def _vinit():
            v_ref[...] = vpart

        @pl.when(n_idx != 0)
        def _vacc():
            v_ref[...] += vpart


@functools.partial(jax.jit, static_argnames=("k", "bn", "bd", "interpret"))
def cluster_sum_pallas(x: jax.Array, a: jax.Array, k: int, *,
                       weights: jax.Array | None = None, bn: int = 256,
                       bd: int = 256, interpret: bool = False):
    """S (k, d) f32, v (k,) f32 — weighted per-cluster sums of x by a.

    Padded points get weight 0 (and cluster 0) so they contribute
    nothing. ``bn`` and ``bd`` must be TPU tiles (`plan.check_tile`).
    """
    check_tile("cluster_sum_pallas", bn=bn, bd=bd)
    n, d = x.shape
    kp = k + (-k % 8)                              # sublane multiple
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    n_pad = -n % bn
    d_pad = -d % bd
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
    np_, dp = x.shape

    row = pl.BlockSpec((1, bn), lambda di, ni: (0, ni))
    vmem = vmem_limit_bytes(
        "cluster_sum_pallas",
        blocks=[(bn, bd), (1, bn), (1, bn), (kp, bd), (kp, 1)],
        temps=[(kp, bn)] * 3 + [(kp, bd)])
    s, v = pl.pallas_call(
        functools.partial(_cluster_sum_kernel, kp=kp),
        grid=(dp // bd, np_ // bn),
        in_specs=[pl.BlockSpec((bn, bd), lambda di, ni: (ni, di)), row, row],
        out_specs=[
            pl.BlockSpec((kp, bd), lambda di, ni: (0, di)),
            pl.BlockSpec((kp, 1), lambda di, ni: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        # the (kp, 1) counts output block is revisited across BOTH grid
        # dims (it is only written when d_idx == 0), so the d dimension
        # must be sequential too — revisited output blocks are illegal on
        # parallel dims in Mosaic.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x, to_row(a, n_pad, 0), to_row(weights, n_pad, 0.0))
    return s[:k, :d], v[:k, 0]
