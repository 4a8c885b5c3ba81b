"""Immutable, versioned codebook snapshots for lock-free readers.

A `CodebookSnapshot` is the unit of publication in `repro.serve`: the
refresher thread builds a NEW snapshot from the estimator's
`export_codebook()` and swaps it into a single reference
(`SnapshotRef.publish`). Reader threads load that reference once per
request — a plain attribute read, atomic under the interpreter — and
then work exclusively on the immutable snapshot they got. There is no
reader lock, and a reader can never observe a half-updated codebook:
either it sees the old snapshot or the new one, both internally
consistent (the `checksum` field lets tests and paranoid callers verify
exactly that).

The predict/transform closures are module-level jitted functions over
``(X, C)`` — NOT per-snapshot jits — so successive snapshots of the same
``(k, d)`` reuse one compiled executable and publishing stays O(copy).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.engines.base import profiler_span
from repro.kernels import ops, ref


@partial(jax.jit, static_argnames=("backend",))
def _predict_jit(X, C, *, backend: Optional[str]):
    a, d1, _ = ops.assign_top2(X, C, backend=backend)
    return a, d1


@jax.jit
def _transform_jit(X, C):
    d2 = ref.pairwise_dist2(X, C)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def codebook_checksum(centroids: np.ndarray, counts: np.ndarray,
                      version: int) -> float:
    """Order-independent fingerprint binding (version, C, v) together.

    float64 sums are cheap, deterministic for a fixed array, and any
    torn mix of two snapshots' buffers changes the value with
    overwhelming probability (the version term keeps two refreshes that
    happen to share centroids distinguishable).
    """
    return float(np.sum(centroids, dtype=np.float64)
                 + 0.5 * np.sum(counts, dtype=np.float64)
                 + 1e-3 * version)


@dataclasses.dataclass(frozen=True)
class CodebookSnapshot:
    """One published codebook: centroids + counts + inference closures.

    ``version`` is assigned by the publisher and strictly increases;
    ``created_at`` is a `time.monotonic` stamp (age, not wall time).
    Arrays are read-only numpy views — mutating them raises.
    """
    version: int
    centroids: np.ndarray        # (k, d) float32, read-only
    counts: np.ndarray           # (k,)  float32, read-only
    n_rounds: int                # estimator rounds folded in so far
    batch_mse: float             # last refresh's batch MSE
    created_at: float            # time.monotonic at publication
    checksum: float              # codebook_checksum(C, v, version)
    kernel_backend: Optional[str] = None

    @classmethod
    def create(cls, version: int, exported: dict, *,
               kernel_backend: Optional[str] = None) -> "CodebookSnapshot":
        """Build from `NestedKMeans.export_codebook()` output."""
        C = np.ascontiguousarray(exported["centroids"], dtype=np.float32)
        v = np.ascontiguousarray(exported["counts"], dtype=np.float32)
        C.setflags(write=False)
        v.setflags(write=False)
        return cls(version=version, centroids=C, counts=v,
                   n_rounds=int(exported["n_rounds"]),
                   batch_mse=float(exported["batch_mse"]),
                   created_at=time.monotonic(),
                   checksum=codebook_checksum(C, v, version),
                   kernel_backend=kernel_backend)

    # -- shape ---------------------------------------------------------------

    @property
    def k(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    def age_s(self) -> float:
        return time.monotonic() - self.created_at

    def verify(self) -> bool:
        """Recompute the checksum — False would mean a torn read."""
        return self.checksum == codebook_checksum(
            self.centroids, self.counts, self.version)

    # -- inference (pure reads, safe from any thread) ------------------------

    # A request opens ``repro.predict`` on the profiler's host plane,
    # holding ``repro.predict.put`` (rows and codebook to the device),
    # ``.dispatch`` (the jitted call) and ``.fetch`` (waiting for the
    # device, and the answer to the host).

    def predict(self, X) -> np.ndarray:
        """Nearest-centroid index for each row of ``X``."""
        with profiler_span("predict"):
            a, _ = self._assign(X)
            with profiler_span("predict.fetch"):
                return np.asarray(a)

    def predict_with_distance(self, X):
        """(labels, euclidean distance to the assigned centroid)."""
        with profiler_span("predict"):
            a, d1 = self._assign(X)
            with profiler_span("predict.fetch"):
                return (np.asarray(a),
                        np.asarray(np.sqrt(np.maximum(d1, 0.0))))

    def _assign(self, X):
        with profiler_span("predict.put"):
            X, C = jnp.asarray(X), jnp.asarray(self.centroids)
        with profiler_span("predict.dispatch"):
            return _predict_jit(X, C, backend=self.kernel_backend)

    def transform(self, X) -> np.ndarray:
        """Euclidean distance of each row to every centroid: (n, k)."""
        return np.asarray(_transform_jit(jnp.asarray(X),
                                         jnp.asarray(self.centroids)))


class SnapshotRef:
    """The single mutable cell readers poll: atomic swap, monotone version.

    `publish` is called by ONE writer (the refresher); `load` by any
    number of readers. The version check on publish turns an accidental
    second writer into a loud error instead of a silently regressing
    snapshot stream.
    """

    def __init__(self):
        self._snap: Optional[CodebookSnapshot] = None

    def load(self) -> Optional[CodebookSnapshot]:
        return self._snap

    def publish(self, snap: CodebookSnapshot) -> None:
        cur = self._snap
        if cur is not None and snap.version <= cur.version:
            raise ValueError(
                f"snapshot version must be monotone: {snap.version} after "
                f"{cur.version} (two writers?)")
        self._snap = snap   # atomic reference swap
