"""Fused pairwise-distance + top-2 argmin Pallas TPU kernel.

The k-means assignment hot spot. For a tile of points the MXU computes
the (bk, bn) Gram block of a centroid tile against a row tile while the
VPU fuses the ``|x|^2 - 2 x.c + |c|^2`` expansion and a running (min,
2nd-min, argmin) reduction carried across the centroid grid dimension in
the (revisited) output blocks.

Grid: (n_blocks, k_blocks) with the k dimension sequential ("arbitrary")
so output blocks act as accumulators; the point dimension is parallel.

Layout as in `fused_round`: centroids on sublanes, rows on lanes, so the
per-row outputs are lane-dense (1, bn) blocks of (1, n) arrays. d is kept
whole per tile; the plan bounds the (bn, d) X tile (`plan.tile_fits`).

Padded centroids carry +inf norms so they can never win the argmin; padded
points produce garbage rows that the wrapper slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_round import dist2_block, top2
from repro.kernels.plan import check_tile, vmem_limit_bytes


def _assign_kernel(x_ref, c_ref, cn_ref, a_ref, d1_ref, d2_ref, *, bk: int):
    """One (i, k) grid step: fold centroid tile k into running top-2."""
    k_idx = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)             # (bn, d)
    d2 = dist2_block(x, c_ref[...].astype(jnp.float32), cn_ref[...])
    bi, b1, b2, _ = top2(d2)                       # each (1, bn)
    bi = bi + k_idx * bk                           # global index

    @pl.when(k_idx == 0)
    def _init():
        a_ref[...] = bi
        d1_ref[...] = b1
        d2_ref[...] = b2

    @pl.when(k_idx != 0)
    def _fold():
        r1 = d1_ref[...]
        r2 = d2_ref[...]
        ri = a_ref[...]
        new1 = jnp.minimum(r1, b1)
        newi = jnp.where(b1 < r1, bi, ri)
        new2 = jnp.minimum(jnp.maximum(r1, b1), jnp.minimum(r2, b2))
        a_ref[...] = newi
        d1_ref[...] = new1
        d2_ref[...] = new2


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def assign_top2_pallas(x: jax.Array, c: jax.Array, *, bn: int = 256,
                       bk: int = 128, interpret: bool = False):
    """(a, d1, d2) = fused nearest/2nd-nearest centroid search.

    x: (n, d); c: (k, d). Returns int32 (n,), f32 (n,), f32 (n,) with
    SQUARED distances. n is padded to bn, k to bk internally; both must
    be TPU tiles (`plan.check_tile`).
    """
    check_tile("assign_top2_pallas", bn=bn, bk=bk)
    n, d = x.shape
    k = c.shape[0]
    n_pad = -n % bn
    k_pad = -k % bk

    cn = jnp.sum(c.astype(jnp.float32) ** 2, axis=1)
    if k_pad:
        c = jnp.pad(c, ((0, k_pad), (0, 0)))
        cn = jnp.pad(cn, (0, k_pad), constant_values=jnp.inf)
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    np_, kp = x.shape[0], c.shape[0]

    row = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    vmem = vmem_limit_bytes(
        "assign_top2_pallas",
        blocks=[(bn, d), (bk, d), (bk, 1)] + [(1, bn)] * 3,
        temps=[(bk, bn)] * 6)
    a, d1, d2 = pl.pallas_call(
        functools.partial(_assign_kernel, bk=bk),
        grid=(np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x, c, cn[:, None])
    return a[0, :n], d1[0, :n], d2[0, :n]
