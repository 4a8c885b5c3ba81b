"""Kernel dispatch plane: one resolved `KernelPlan` per fit.

An engine (or `ops` itself, for legacy string callers) calls
`resolve_plan` ONCE and threads the frozen result everywhere a kernel is
launched.

The plan is keyed on the (b, k, d) **pow2 bucket lattice** — the same
lattice `api.loop` uses for jit cache buckets — so a fit whose nested
batch doubles through b0, 2*b0, ... N shares one plan for the whole
trajectory (the bucket is taken at b_max). Because `KernelPlan` is a
frozen dataclass it is hashable with a stable repr, which lets the
engines put it straight into `jax.jit` static args and into
`util.tracecount` statics without widening the retrace auditor's
bucket key.

Block sizes (bn rows / bk centroid cols / bd feature cols) come from a
deterministic table (`_table_blocks`) that only returns tiles the TPU
compiler accepts (`tile_fits`), so a fit's blocks depend on committed
code alone. An explicit ``REPRO_TUNE_KERNELS`` run may instead time the
legal candidates and keep the winner under ``artifacts/tune/``; only
such a run reads or writes that cache. Tuning can only ever change
performance, never results.

Tile rules shared with the kernels (`check_tile`, `vmem_limit_bytes`):
per-row vectors travel as lane-dense ``(1, n)`` rows, so every row tile
and feature tile is a multiple of the 128-lane width, and each kernel
asks the compiler for the VMEM its blocks and temporaries need.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

_TUNE_ENV = "REPRO_TUNE_KERNELS"
_TUNE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "tune"

#: tuner candidate grid — small on purpose: at most 12 timed points per
#: bucket, fewer where `tile_fits` drops a row tile for large k or d.
_CANDIDATES = tuple((bn, bk, bd)
                    for bn in (128, 256, 512)
                    for bk in (128, 256)
                    for bd in (128, 256))

#: TPU vector lane width: the minor dim of every block is a multiple.
LANE = 128
#: (kp, bn) f32 elements one kernel temporary may hold (1 MiB).
_TEMP_ELEMS = 1 << 18
#: (bn, d) f32 elements one X tile may hold (4 MiB).
_XTILE_ELEMS = 1 << 20
#: VMEM a kernel may ask for: a TPU v5e core has 128 MiB; the rest is
#: left to the compiler's own scratch.
VMEM_CAP_BYTES = 100 << 20
_VMEM_DEFAULT_BYTES = 16 << 20        # the compiler's scoped default


def _pad_to(x: int, m: int) -> int:
    return x + (-x % m)


def check_tile(kernel: str, **tiles: int) -> None:
    """Reject a tile the TPU compiler refuses, in every mode.

    Row tiles are lane dims of the ``(1, bn)`` per-row blocks, and
    feature / centroid tiles are lane or MXU dims, so each must be a
    positive multiple of 128. Interpret mode would accept any size; it
    is checked all the same, so a tile that only the CPU runs fails
    here and not first on the chip.
    """
    for name, value in tiles.items():
        if value < LANE or value % LANE:
            raise ValueError(
                f"{kernel}: {name}={value} is not a TPU tile; it must be a "
                f"positive multiple of {LANE}")


def vmem_limit_bytes(kernel: str, blocks, temps) -> int:
    """The scoped-VMEM limit for one kernel launch, or a clear error.

    ``blocks``: (rows, cols) of each pipelined f32/i32 block, counted
    twice for double buffering. ``temps``: (rows, cols) of each
    tile-sized temporary the body keeps live. Shapes are padded to the
    (8, 128) VMEM tiling. The limit is twice the estimate (the compiler
    keeps more live than the body names), at least the compiler's
    default and at most `VMEM_CAP_BYTES`.
    """
    def nbytes(rows: int, cols: int) -> int:
        return _pad_to(rows, 8) * _pad_to(cols, LANE) * 4

    need = (2 * sum(nbytes(*b) for b in blocks)
            + sum(nbytes(*t) for t in temps))
    if need > VMEM_CAP_BYTES:
        raise ValueError(
            f"{kernel}: blocks {list(blocks)} and temporaries {list(temps)} "
            f"need ~{need >> 20} MiB of VMEM, over the {VMEM_CAP_BYTES >> 20}"
            f" MiB a kernel may use; use a smaller row tile")
    return int(min(VMEM_CAP_BYTES, max(_VMEM_DEFAULT_BYTES, 2 * need)))


def tile_fits(bn: int, k: int, d: int) -> bool:
    """Whether row tile ``bn`` keeps every kernel's (kp, bn) temporaries
    and (bn, d) X tile within their VMEM budgets at this (k, d)."""
    return (_pad_to(k, LANE) * bn <= _TEMP_ELEMS
            and bn * _pad_to(d, LANE) <= _XTILE_ELEMS)


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return 1 << max(0, int(x) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Resolved kernel dispatch for one fit.

    Frozen + hashable: engines pass the plan through jit static args,
    so everything here must be decided before tracing and constant for
    the fit's lifetime.
    """

    backend: str                    # "ref" | "pallas"
    interpret: bool                 # pallas interpret mode (CPU)
    bn: int                         # rows per point tile
    bk: int                         # centroid columns per assign tile
    bd: int                         # feature columns per cluster-sum tile
    bucket: Tuple[int, int, int]    # pow2 (b, k, d) lattice cell
    source: str                     # "table" | "tuned" | "cached"
    family: str = "unset"           # bound family the plan serves — the
                                    # fused pallas round only covers
                                    # none/hamerly2; elkan/exponion route
                                    # through the per-op kernels, and
                                    # manifests need the plan itself to
                                    # say which shape a fit actually ran

    def row_tile(self, n: int) -> int:
        """Row tile for a launch over ``n`` rows: no larger than the
        (pow2-padded) batch, so a plan resolved at b_max still launches
        sane grids for the small early nested rounds, and never below
        one lane width, so it stays a TPU tile."""
        return max(LANE, min(self.bn, next_pow2(n)))

    def to_dict(self) -> Dict[str, Any]:
        """JSON form for benchmark manifests / FitOutcome."""
        return {"backend": self.backend, "interpret": self.interpret,
                "bn": self.bn, "bk": self.bk, "bd": self.bd,
                "bucket": list(self.bucket), "source": self.source,
                "family": self.family}


def _table_blocks(bucket: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Deterministic block sizes for a bucket, all of them TPU tiles.

    bn is the largest power of two in [128, 1024] that the batch bucket
    covers and that `tile_fits` the bucket's (k, d) — 1024 at the paper's
    k=50, d=784 and 256 at k=1024. bk is one MXU tile; bd widens for
    high-dimensional data so the cluster-sum grid does not degenerate
    into tiny feature strips.
    """
    bp2, kp2, dp2 = bucket
    bn = 1024
    while bn > LANE and (bn > bp2 or not tile_fits(bn, kp2, dp2)):
        bn //= 2
    bk = LANE
    bd = 256 if dp2 >= 256 else LANE
    return bn, bk, bd


def _cache_path(platform: str, bucket: Tuple[int, int, int]) -> Path:
    b, k, d = bucket
    return _TUNE_DIR / f"{platform}-b{b}-k{k}-d{d}.json"


def _tune_blocks(platform: str,
                 bucket: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Time the candidate grid on bucket-shaped synthetic data.

    Sizes are clamped so interpret-mode tuning on CPU stays in seconds;
    the measured op mix (assign + cluster-sum) is the nested round's
    inner loop, so the argmin transfers.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.cluster_sum import cluster_sum_pallas
    from repro.kernels.kmeans_assign import assign_top2_pallas

    bp2, kp2, dp2 = bucket
    n = int(min(bp2, 2048))
    k = int(min(kp2, 512))
    d = int(min(dp2, 512))
    kp = k + (-k % 128)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    a = jnp.asarray(rng.integers(0, k, size=n), jnp.int32)
    interpret = platform == "cpu"

    best: Optional[Tuple[float, int, int, int]] = None
    for bn, bk, bd in _CANDIDATES:
        if not tile_fits(bn, kp2, dp2):
            continue
        bn_eff = max(LANE, min(bn, next_pow2(n)))

        def run() -> None:
            out = assign_top2_pallas(x, c, bn=bn_eff, bk=min(bk, kp),
                                     interpret=interpret)
            sums = cluster_sum_pallas(x, a, kp, bn=bn_eff, bd=bd,
                                      interpret=interpret)
            jax.block_until_ready((out, sums))

        run()                                    # compile / warm
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        if best is None or dt < best[0]:
            best = (dt, bn, bk, bd)
    assert best is not None
    return best[1], best[2], best[3]


@functools.lru_cache(maxsize=None)
def _resolve_cached(kernel_backend: Optional[str],
                    bucket: Tuple[int, int, int],
                    platform: str, tune: bool,
                    family: str) -> KernelPlan:
    from repro.util.env import apply_kernel_flags

    # Satellite of the dispatch refactor: the env-module flag shaping is
    # applied on the SAME path that decides to launch kernels, so a fit
    # that resolves a plan gets the platform's XLA flags without its
    # launcher having called set_platform.
    apply_kernel_flags(platform)

    backend = kernel_backend or ("pallas" if platform == "tpu" else "ref")
    if backend == "pallas" and platform not in ("tpu", "cpu"):
        raise ValueError(
            f"kernel_backend='pallas' needs a TPU (compiled) or the CPU "
            f"(interpreted); platform {platform!r} has neither")
    bn, bk, bd = _table_blocks(bucket)
    source = "table"
    if tune:
        path = _cache_path(platform, bucket)
        try:
            blob = json.loads(path.read_text())
            bn, bk, bd = int(blob["bn"]), int(blob["bk"]), int(blob["bd"])
            source = "cached"
        except (ValueError, KeyError, OSError):
            bn, bk, bd = _tune_blocks(platform, bucket)
            source = "tuned"
            try:
                _TUNE_DIR.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(
                    {"platform": platform, "bucket": list(bucket),
                     "bn": bn, "bk": bk, "bd": bd}, sort_keys=True) + "\n")
            except OSError:
                pass                # read-only checkout: keep the result
    return KernelPlan(backend=backend, interpret=(platform == "cpu"),
                      bn=bn, bk=bk, bd=bd, bucket=bucket, source=source,
                      family=family)


def resolve_plan(kernel_backend: Optional[str] = None, *, b: int, k: int,
                 d: int, platform: Optional[str] = None,
                 tune: Optional[bool] = None,
                 bounds: Optional[str] = None) -> KernelPlan:
    """Resolve ``config.kernel_backend`` into a per-fit `KernelPlan`.

    Call once per fit with the fit's maximum batch (b), k and d; the
    result is cached per (backend, bucket, platform, family), so the
    legacy per-call path through `ops` pays only a dict lookup.

      kernel_backend  None (auto: pallas iff TPU) | "ref" | "pallas"
      platform        defaults to ``jax.default_backend()``
      tune            defaults to the ``REPRO_TUNE_KERNELS`` env var; only
                      a tuning run reads or writes ``artifacts/tune/``
      bounds          the fit's bound family, recorded on the plan for
                      manifests (elkan/exponion never take the fused
                      pallas round — the plan should say so). Purely
                      informational: block sizes don't depend on it.
    """
    if kernel_backend not in (None, "ref", "pallas"):
        raise ValueError(f"unknown kernel_backend {kernel_backend!r}")
    if platform is None:
        import jax
        platform = jax.default_backend()
    if tune is None:
        tune = os.environ.get(_TUNE_ENV, "") not in ("", "0")
    bucket = (next_pow2(b), next_pow2(k), next_pow2(d))
    return _resolve_cached(kernel_backend, bucket, str(platform),
                           bool(tune), bounds or "unset")
