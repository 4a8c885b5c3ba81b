"""Pallas kernels vs pure-jnp oracles, interpret=True shape/dtype sweeps;
the `kernels.plan` dispatch layer and its TPU tile rules."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.cluster_sum import cluster_sum_pallas
from repro.kernels.kmeans_assign import assign_top2_pallas
from repro.kernels.plan import KernelPlan, next_pow2, resolve_plan

SHAPES = [
    (64, 7, 5),          # tiny, heavy padding
    (256, 32, 50),       # paper k
    (300, 784, 50),      # infMNIST dims, unaligned n
    (512, 128, 128),     # aligned everything
    (1000, 200, 257),    # k crosses one block boundary
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_assign_top2_matches_ref(n, d, k, dtype):
    rng = np.random.default_rng(n + d + k)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    c = jnp.asarray(rng.normal(size=(k, d)) * 2, dtype)
    a_p, d1_p, d2_p = assign_top2_pallas(x, c, bn=128, bk=128,
                                         interpret=True)
    a_r, d1_r, d2_r = ref.assign_top2_ref(x, c)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(d1_p, d1_r, rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(d2_p, d2_r, rtol=tol, atol=tol * 10)
    # assignments may differ only where d1 ties within tolerance
    diff = np.asarray(a_p) != np.asarray(a_r)
    if diff.any():
        d2m = ref.pairwise_dist2(x, c)
        for i in np.where(diff)[0]:
            assert abs(d2m[i, a_p[i]] - d2m[i, a_r[i]]) < tol * 100


@pytest.mark.parametrize("n,d,k", SHAPES)
def test_cluster_sum_matches_ref(n, d, k):
    rng = np.random.default_rng(n * 7 + d)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    a = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    w = jnp.asarray(rng.choice([-1.0, 0.0, 1.0], n), jnp.float32)
    kp = k + (-k % 128)
    s_p, v_p = cluster_sum_pallas(x, a, kp, weights=w, bn=128, bd=128,
                                  interpret=True)
    s_r, v_r = ref.cluster_sum_ref(x, a, k, weights=w)
    np.testing.assert_allclose(s_p[:k], s_r, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(v_p[:k], v_r, rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(s_p[k:]) == 0)


def test_assign_top2_second_distance_is_true_second():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    _, d1, d2 = assign_top2_pallas(x, c, bn=128, bk=128, interpret=True)
    d2m = np.sort(np.asarray(ref.pairwise_dist2(x, c)), axis=1)
    np.testing.assert_allclose(d1, d2m[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d2, d2m[:, 1], rtol=1e-5, atol=1e-5)


def test_ops_wrappers_roundtrip():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(200, 33)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(7, 33)), jnp.float32)
    for backend in ("ref", "pallas"):
        a, d1, d2 = ops.assign_top2(x, c, backend=backend)
        s, v = ops.cluster_sum(x, a, 7, backend=backend)
        assert a.shape == (200,) and s.shape == (7, 33) and v.shape == (7,)
        np.testing.assert_allclose(
            np.asarray(v).sum(), 200.0, rtol=1e-6)


@pytest.mark.parametrize("n,d,k", [(100, 16, 5), (256, 64, 32),
                                   (300, 48, 7)])
def test_fused_round_matches_ref(n, d, k):
    from repro.kernels.fused_round import fused_round_pallas, fused_round_ref
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)) * 2, jnp.float32)
    a_p, d1_p, d2_p, S_p, v_p, sse_p = fused_round_pallas(
        x, c, bn=128, interpret=True)
    a_r, d1_r, d2_r, S_r, v_r, sse_r = fused_round_ref(x, c)
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))
    np.testing.assert_allclose(d1_p, d1_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(d2_p, d2_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S_p, S_r, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(v_p, v_r, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(sse_p, sse_r, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,d,k", [(100, 16, 5), (256, 64, 32),
                                   (300, 48, 7), (64, 129, 7)])
def test_fused_nested_round_matches_ref(n, d, k):
    """The PR 9 fused nested round (assign + Hamerly keep + delta-S/v
    in one pass) vs its jnp oracle: labels exact, accumulators close —
    including awkward shapes (k % 128 != 0, n % bn != 0, d non-tile)
    and pad rows (a_prev=-1 / settled / invalid) contributing zero."""
    from repro.kernels.fused_round import (fused_nested_round_pallas,
                                           fused_nested_round_ref)
    rng = np.random.default_rng(n * 3 + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)) * 2, jnp.float32)
    a_prev = jnp.asarray(rng.integers(-1, k, size=n), jnp.int32)
    settled = jnp.asarray(rng.random(n) < 0.3)
    d_keep = jnp.asarray(rng.random(n), jnp.float32)
    lb_keep = jnp.asarray(rng.random(n), jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    args = (x, c, a_prev, settled, d_keep, lb_keep, valid)
    a_p, d_p, lb_p, S_p, v_p, sse_p = fused_nested_round_pallas(
        *args, bn=128, interpret=True)
    a_r, d_r, lb_r, S_r, v_r, sse_r = fused_nested_round_ref(*args)
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))
    np.testing.assert_allclose(d_p, d_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(lb_p, lb_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S_p, S_r, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(v_p, v_r, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(sse_p, sse_r, rtol=1e-4, atol=1e-3)


# -- the dispatch plan -------------------------------------------------------

def test_resolve_plan_auto_rule():
    """auto (kernel_backend=None) resolves to ref off-TPU, and the
    explicit spellings are honoured verbatim."""
    import jax
    plan = resolve_plan(None, b=1024, k=16, d=8)
    expect = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert plan.backend == expect
    assert resolve_plan("ref", b=1024, k=16, d=8).backend == "ref"
    p = resolve_plan("pallas", b=1024, k=16, d=8)
    assert p.backend == "pallas"
    assert p.interpret == (jax.default_backend() != "tpu")
    with pytest.raises(ValueError):
        resolve_plan("cuda", b=1024, k=16, d=8)


def test_resolve_plan_bucketing_and_cache():
    """Shapes in the same pow2 bucket share ONE cached plan object
    (identity — the lru_cache is what keeps jit statics stable);
    different buckets get different plans."""
    a = resolve_plan("pallas", b=1000, k=16, d=8)
    b = resolve_plan("pallas", b=700, k=13, d=5)    # same pow2 bucket
    assert a is b
    assert a.bucket == (1024, 16, 8)
    c = resolve_plan("pallas", b=1025, k=16, d=8)
    assert c is not a and c.bucket[0] == 2048


def test_plan_blocks_and_to_dict():
    plan = resolve_plan("pallas", b=4096, k=200, d=300)
    assert plan.bk == 128 and plan.bd in (128, 256)
    assert 128 <= plan.bn <= 1024 and plan.bn % 128 == 0
    assert plan.source in ("table", "tuned", "cached")
    d = plan.to_dict()
    assert d["backend"] == "pallas" and tuple(d["bucket"]) == plan.bucket
    # frozen + hashable: the plan rides in jit static args
    assert hash(plan) == hash(KernelPlan(**{
        f: getattr(plan, f) for f in
        ("backend", "interpret", "bn", "bk", "bd", "bucket", "source")}))
    assert next_pow2(5) == 8 and next_pow2(8) == 8 and next_pow2(1) == 1


def test_ops_dispatch_through_plan_awkward_shapes():
    """ops.assign_top2 / cluster_sum / fused_nested_round driven by a
    resolved plan (not a backend string) at shapes off every tile
    boundary, weighted included."""
    from repro.kernels import ops
    rng = np.random.default_rng(7)
    n, d, k = 321, 19, 37                  # nothing divides anything
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)) * 2, jnp.float32)
    w = jnp.asarray(rng.choice([0.5, 1.0, 2.0], n), jnp.float32)
    plan = resolve_plan("pallas", b=n, k=k, d=d)
    a_p, d1_p, d2_p = ops.assign_top2(x, c, plan=plan)
    a_r, d1_r, d2_r = ref.assign_top2_ref(x, c)
    np.testing.assert_allclose(d1_p, d1_r, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(d2_p, d2_r, rtol=1e-5, atol=1e-4)
    s_p, v_p = ops.cluster_sum(x, a_p, k, weights=w, plan=plan)
    s_r, v_r = ref.cluster_sum_ref(x, a_r, k, weights=w)
    np.testing.assert_allclose(s_p, s_r, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(v_p, v_r, rtol=1e-5, atol=1e-5)
    # ref plan routes to the oracles exactly
    rp = resolve_plan("ref", b=n, k=k, d=d)
    a2, _, _ = ops.assign_top2(x, c, plan=rp)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a_r))


# -- TPU tile rules ----------------------------------------------------------

def _call_assign(bn, bk):
    x = jnp.zeros((8, 16), jnp.float32)
    assign_top2_pallas(x, x[:4], bn=bn, bk=bk, interpret=True)


def _call_cluster_sum(bn, bd):
    x = jnp.zeros((8, 16), jnp.float32)
    cluster_sum_pallas(x, jnp.zeros((8,), jnp.int32), 4, bn=bn, bd=bd,
                       interpret=True)


def _call_fused(bn, _unused):
    from repro.kernels.fused_round import fused_round_pallas
    x = jnp.zeros((8, 16), jnp.float32)
    fused_round_pallas(x, x[:4], bn=bn, interpret=True)


@pytest.mark.parametrize("call,tiles", [
    (_call_assign, (64, 128)), (_call_assign, (128, 8)),
    (_call_cluster_sum, (8, 128)), (_call_cluster_sum, (128, 200)),
    (_call_fused, (64, None)), (_call_fused, (192 + 1, None)),
])
def test_wrappers_reject_non_tpu_tiles(call, tiles):
    """Every wrapper refuses a tile the TPU compiler would refuse, in
    interpret mode too, with an error that names the tile."""
    with pytest.raises(ValueError, match="not a TPU tile"):
        call(*tiles)


@pytest.mark.parametrize("b", [1, 100, 5000, 400_000])
@pytest.mark.parametrize("k,d", [(50, 784), (1024, 784), (4096, 128),
                                 (16, 2048)])
def test_table_blocks_are_tpu_tiles(b, k, d):
    """The default plan only ever hands out legal tiles that fit VMEM:
    lane multiples, and a (kp, bn) temporary / (bn, d) X tile within
    `tile_fits` — bn shrinks as k or d grows instead of failing."""
    from repro.kernels.plan import check_tile, tile_fits
    plan = resolve_plan("pallas", b=b, k=k, d=d)
    check_tile("plan", bn=plan.bn, bk=plan.bk, bd=plan.bd)
    assert plan.source == "table"
    assert tile_fits(plan.bn, k, d) or plan.bn == 128
    if (b, k, d) == (400_000, 50, 784):
        assert plan.bn == 1024
    if (b, k, d) == (400_000, 1024, 784):
        assert plan.bn == 256


def test_kernels_use_pltpu_compiler_params():
    """The kernels build `pltpu.CompilerParams` directly, with the
    dimension semantics and an explicit scoped-VMEM limit."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.plan import VMEM_CAP_BYTES, vmem_limit_bytes
    cp = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                              vmem_limit_bytes=vmem_limit_bytes(
                                  "t", blocks=[(256, 784)], temps=[]))
    assert tuple(cp.dimension_semantics) == ("arbitrary",)
    assert 16 << 20 <= cp.vmem_limit_bytes <= VMEM_CAP_BYTES
    with pytest.raises(ValueError, match="VMEM"):
        vmem_limit_bytes("t", blocks=[(8192, 8192)], temps=[])


# -- the end-to-end smoke ----------------------------------------------------

@pytest.mark.slow
def test_kernel_dispatch_subprocess():
    """scripts/smoke_kernels.py: fused-round op parity, pallas-vs-ref
    fit bit-parity (local tb/gb + XL m=2/m=1), and the retrace/hostsync
    auditors staying green with the plan active. Subprocess-isolated
    because it forces 8 host devices via XLA_FLAGS."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "scripts/smoke_kernels.py"],
                       env=env, capture_output=True, text=True,
                       timeout=600, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("op parity", "local tb (fused hamerly2)",
                   "local gb (fused bounds-free)", "xl (4,2) m=2",
                   "xl (8,1) m=1 (fused)", "kernels smoke OK"):
        assert marker in r.stdout, (marker, r.stdout)
