"""The plain reference that decides ``correct`` for k-means cells.

It imports nothing of the program and takes nothing the program made
but the answers it checks. Everything runs after the measured window, on
the device, in blocks of rows.

Squared distances are taken directly, as the sum over features of
(x - c)^2 in float32, for the few centroids that can be nearest: the
row's own label and the three nearest by the expanded form
|x|^2 - 2 x.c + |c|^2 at ``HIGHEST`` precision. The direct form carries
no cancellation error, so a gap it reports is the answer's own.

Numbers, each a fault of a different kind:

  unlabeled     rows whose label is not a centroid index (exact, 0);
  label_gap     the widest amount by which a row's labelled centroid
                lies farther than its nearest one, over the mean
                squared distance to the nearest centroid;
  centroid_gap  (fits) the widest distance between a centroid and the
                mean of the rows labelled with it, over the root of
                that same mean squared distance.

A fit that converged is a fixed point of Lloyd's iteration: each row
sits with its nearest centroid and each centroid is the mean of its
rows. Both hold to rounding in float32; a lower precision, a step that
leaves its state unchanged, a mean over part of the rows, or an altered
label breaks one of them.

The configurations state float32 rows, centroids and distances. The
control (`lloyd_fit`, `assign`) is the same arithmetic at the next
precision down: matrix products of bfloat16 operands summed in float32,
as a one-pass bfloat16 matrix unit computes them.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 8192
CANDIDATES = 3


def _dot_bf16(a, b):
    """a @ b with both operands rounded to bfloat16 and the products
    summed in float32, as a one-pass bfloat16 matrix unit computes it.
    Products of bfloat16 values are exact in float32, so this computes
    the same on every platform."""
    def bf(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(bf(a), bf(b), precision=HIGHEST)


def _expanded(x, c, low: bool):
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    xc = _dot_bf16(x, c.T) if low else jnp.dot(x, c.T, precision=HIGHEST)
    return xn - 2.0 * xc + cn


@jax.jit
def _block_gaps(x, c, a):
    """Per row: (direct d^2 to the labelled centroid, direct d^2 to the
    nearest, label valid)."""
    k = c.shape[0]
    valid = (a >= 0) & (a < k)
    a_safe = jnp.clip(a, 0, k - 1)
    near = jax.lax.top_k(-_expanded(x, c, False), min(CANDIDATES, k))[1]
    cand = jnp.concatenate([a_safe[:, None], near], axis=1)
    d2 = jnp.sum((x[:, None, :] - c[cand]) ** 2, axis=2)
    return d2[:, 0], jnp.min(d2, axis=1), valid


@partial(jax.jit, static_argnames=("k",))
def _block_residual_sums(x, c, a, *, k: int):
    """Per centroid: sum of (x - c_a) over its rows, and the row count."""
    a_safe = jnp.clip(a, 0, k - 1)
    w = ((a >= 0) & (a < k)).astype(jnp.float32)
    r = (x - c[a_safe]) * w[:, None]
    return (jax.ops.segment_sum(r, a_safe, num_segments=k),
            jax.ops.segment_sum(w, a_safe, num_segments=k))


def _blocks(n: int, block: int = BLOCK) -> Iterable[Tuple[int, int]]:
    for lo in range(0, n, block):
        yield lo, min(n, lo + block)


def _row_gaps(X: np.ndarray, C: np.ndarray, labels: np.ndarray,
              rows: Optional[np.ndarray] = None):
    """Host arrays of per-row (d2 labelled, d2 nearest, valid).

    ``rows`` (optional) indexes the rows of ``X`` that ``labels`` label,
    in order; without it ``labels`` labels every row of ``X``.
    """
    c = jnp.asarray(C, jnp.float32)
    Xd = jnp.asarray(X) if rows is not None else None
    n = len(labels)
    out = [np.empty(n, np.float32), np.empty(n, np.float32),
           np.empty(n, bool)]
    for lo, hi in _blocks(n):
        if rows is None:
            x = jnp.asarray(X[lo:hi], jnp.float32)
        else:
            x = Xd[jnp.asarray(rows[lo:hi])]
        res = _block_gaps(x, c, jnp.asarray(labels[lo:hi], jnp.int32))
        for buf, r in zip(out, jax.device_get(res)):
            buf[lo:hi] = r
    return out


def label_rows(X: np.ndarray, C: np.ndarray, labels: np.ndarray,
               rows: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    """Per answered row: ``unlabeled`` (1 where the label is no centroid
    index) and ``label_gap`` (over the mean squared distance to the
    nearest centroid of all these rows). ``labels[i]`` answers row
    ``rows[i]`` of ``X``; without ``rows``, row ``i``."""
    d2_lab, d2_min, valid = _row_gaps(
        X, np.asarray(C, np.float32), np.asarray(labels, np.int32),
        rows=None if rows is None else np.asarray(rows, np.int32))
    mse = float(np.mean(d2_min, dtype=np.float64))
    gap = np.where(valid, d2_lab - d2_min, 0.0) / mse
    return {"unlabeled": (~valid).astype(np.float64), "label_gap": gap,
            "mse": mse}


def reduce_rows(per_row: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The numbers compared, over a set of rows."""
    return {"unlabeled": float(np.sum(per_row["unlabeled"])),
            "label_gap": float(np.max(per_row["label_gap"], initial=0.0))}


def check_fit(X: np.ndarray, C: np.ndarray,
              labels: np.ndarray) -> Dict[str, float]:
    """The numbers compared for one fit's centroids and labels."""
    C = np.asarray(C, np.float32)
    labels = np.asarray(labels, np.int32)
    per_row = label_rows(X, C, labels)
    nums = reduce_rows(per_row)
    k = C.shape[0]
    c = jnp.asarray(C)
    R = np.zeros((k, C.shape[1]), np.float64)
    v = np.zeros(k, np.float64)
    for lo, hi in _blocks(len(labels)):
        r, cnt = jax.device_get(_block_residual_sums(
            jnp.asarray(X[lo:hi], jnp.float32), c,
            jnp.asarray(labels[lo:hi]), k=k))
        R += r
        v += cnt
    held = v > 0
    off = np.linalg.norm(R[held] / v[held, None], axis=1)
    nums["centroid_gap"] = float(np.max(off, initial=0.0)) / np.sqrt(
        per_row["mse"])
    return nums


# -- the control: the same arithmetic at the next precision down ----------

@jax.jit
def _assign_low(x, c):
    return jnp.argmin(_expanded(x, c, True), axis=1).astype(jnp.int32)


def assign(X: np.ndarray, C: np.ndarray) -> np.ndarray:
    """Nearest-centroid labels from bfloat16 distances."""
    c = jnp.asarray(C, jnp.float32)
    return np.concatenate([
        np.asarray(_assign_low(jnp.asarray(X[lo:hi], jnp.float32), c))
        for lo, hi in _blocks(len(X))])


@partial(jax.jit, static_argnames=("max_iter",))
def _lloyd_low(X, C0, *, max_iter: int):
    k = C0.shape[0]
    Xb = X.astype(jnp.bfloat16).astype(jnp.float32)
    ones = jnp.ones((X.shape[0],), jnp.float32)

    def assign_all(C):
        return jnp.argmin(_expanded(X, C, True), axis=1).astype(jnp.int32)

    def means(labels, C):
        S = jax.ops.segment_sum(Xb, labels, num_segments=k)
        v = jax.ops.segment_sum(ones, labels, num_segments=k)
        return jnp.where(v[:, None] > 0, S / jnp.maximum(v, 1.0)[:, None], C)

    def body(state):
        it, C, labels, _ = state
        new = assign_all(C)
        return it + 1, means(new, C), new, jnp.any(new != labels)

    labels = assign_all(C0)
    return jax.lax.while_loop(
        lambda s: (s[0] < max_iter) & s[3], body,
        (jnp.int32(1), means(labels, C0), labels, jnp.bool_(True)))


def lloyd_fit(X: np.ndarray, C0: np.ndarray, *,
              max_iter: int = 300) -> Tuple[np.ndarray, np.ndarray, int]:
    """Lloyd's iteration from ``C0`` in bfloat16 (distances and sums),
    until no label changes: (centroids, labels, iterations)."""
    it, C, labels, _ = jax.device_get(_lloyd_low(
        jnp.asarray(X, jnp.float32), jnp.asarray(C0, jnp.float32),
        max_iter=max_iter))
    return np.asarray(C), np.asarray(labels), int(it)
