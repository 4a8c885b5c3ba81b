"""infMNIST-like rows made on the device from a seed.

The recipe of the program's ``data/synthetic.infmnist_like`` (smooth
random stroke prototypes, one per class; a per-row smooth sinusoidal
deformation applied by nearest-pixel lookup; Gaussian pixel noise;
clipped to [0, 1]), written with ``jax.random`` so that 10^6 rows of
784 take about a second on the chip instead of tens of seconds on the
host. It draws from a different random stream than the host recipe.

``make(seed, n, d, **params)`` returns an (n, d) float32 device array,
built in one jitted call; the same seed gives the same rows.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CHUNK = 32768


def key_for(seed: int, stream: int = 0):
    """A key that keeps every bit of a seed wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def _prototypes(key, n_classes: int, side: int):
    """(n_classes, side*side) smooth 'digits' of 2 to 4 strokes each."""
    ks = jax.random.split(key, 7)
    shape = (n_classes, 4)
    cx = jax.random.uniform(ks[0], shape, minval=0.2, maxval=0.8)
    cy = jax.random.uniform(ks[1], shape, minval=0.2, maxval=0.8)
    sx = jax.random.uniform(ks[2], shape, minval=0.05, maxval=0.25)
    sy = jax.random.uniform(ks[3], shape, minval=0.05, maxval=0.25)
    th = jax.random.uniform(ks[4], shape, maxval=math.pi)
    strokes = jax.random.randint(ks[5], (n_classes, 1), 2, 5)
    on = jnp.arange(4)[None, :] < strokes
    g = jnp.arange(side, dtype=jnp.float32) / side
    yy, xx = jnp.meshgrid(g, g, indexing="ij")
    dx = xx[None, None] - cx[..., None, None]
    dy = yy[None, None] - cy[..., None, None]
    c, s = jnp.cos(th)[..., None, None], jnp.sin(th)[..., None, None]
    rx = dx * c + dy * s
    ry = -dx * s + dy * c
    blob = jnp.exp(-(rx ** 2 / (2 * sx[..., None, None] ** 2)
                     + ry ** 2 / (2 * sy[..., None, None] ** 2)))
    img = jnp.sum(jnp.where(on[..., None, None], blob, 0.0), axis=1)
    img = img / jnp.maximum(img.max(axis=(1, 2), keepdims=True), 1e-6)
    return img.reshape(n_classes, side * side)


@partial(jax.jit, static_argnames=("n", "d", "n_classes", "deform",
                                   "noise"))
def _make(key, *, n: int, d: int, n_classes: int, deform: float,
          noise: float):
    side = math.isqrt(d)
    protos = _prototypes(jax.random.fold_in(key, 0), n_classes, side)
    flat = protos.reshape(-1)
    g = jnp.arange(side, dtype=jnp.float32)
    yy, xx = jnp.meshgrid(g, g, indexing="ij")
    rows = min(CHUNK, n)
    n_chunks = -(-n // rows)

    def chunk(i, out):
        ks = jax.random.split(jax.random.fold_in(key, i + 1), 4)
        cls = jax.random.randint(ks[0], (rows,), 0, n_classes)
        ph = jax.random.uniform(ks[1], (rows, 2), maxval=2 * math.pi)
        amp = jax.random.uniform(ks[2], (rows, 2), maxval=deform)
        fx = xx[None] + amp[:, 0, None, None] * jnp.sin(
            yy[None] / side * 2 * math.pi + ph[:, 0, None, None])
        fy = yy[None] + amp[:, 1, None, None] * jnp.sin(
            xx[None] / side * 2 * math.pi + ph[:, 1, None, None])
        xi = jnp.clip(fx, 0, side - 1).astype(jnp.int32)
        yi = jnp.clip(fy, 0, side - 1).astype(jnp.int32)
        idx = cls[:, None, None] * d + yi * side + xi
        img = flat[idx] + noise * jax.random.normal(ks[3],
                                                    (rows, side, side))
        img = jnp.clip(img, 0.0, 1.0).reshape(rows, d)
        # the last chunk ends at row n (it may overlap the one before),
        # so the buffer is written in place and never copied
        start = jnp.minimum(i * rows, n - rows)
        return jax.lax.dynamic_update_slice(out, img, (start, 0))

    return jax.lax.fori_loop(0, n_chunks, chunk,
                             jnp.zeros((n, d), jnp.float32))


def make(seed: int, n: int, d: int, *, stream: int = 0,
         n_classes: int = 10, deform: float = 1.5,
         noise: float = 0.05) -> jax.Array:
    """(n, d) float32 rows in [0, 1] on the device; d must be a square."""
    if math.isqrt(d) ** 2 != d:
        raise ValueError(f"infmnist rows are square images; d={d} is not")
    return _make(key_for(seed, stream), n=n, d=d, n_classes=n_classes,
                 deform=float(deform), noise=float(noise))
