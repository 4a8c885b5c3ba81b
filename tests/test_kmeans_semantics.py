"""Faithfulness tests: every algorithm vs serial/numpy oracles.

The paper's central exactness claims:
  * bound tests never change assignments (tb == gb round-for-round);
  * mb's S/v form (Alg. 8) == the serial running-mean form (Alg. 1);
  * mb-f centroids are the exact mean of CURRENT assignments;
  * gb-inf with b0=N reproduces Lloyd's algorithm.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import driver, rounds
from repro.core.state import init_state, full_mse


def _fit(X, k, **kw):
    return driver.fit(X, k, X_val=None, max_rounds=kw.pop("max_rounds", 40),
                      **kw)


# ---------------------------------------------------------------------------
# bounding is exact: tb (either bound type) == gb assignments every round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bounds", ["hamerly2", "elkan", "exponion"])
def test_bounds_never_change_assignments(blobs, bounds):
    X, _ = blobs
    k, b = 8, 512
    Xd = jnp.asarray(X)
    s_ref = init_state(Xd, k, bounds="none")
    s_tb = init_state(Xd, k, bounds=bounds)
    for r in range(12):
        s_ref, _ = rounds.nested_round(Xd, s_ref, b=b, rho=np.inf,
                                       bounds="none")
        s_tb, info = rounds.nested_round(Xd, s_tb, b=b, rho=np.inf,
                                         bounds=bounds)
        np.testing.assert_array_equal(np.asarray(s_ref.points.a[:b]),
                                      np.asarray(s_tb.points.a[:b]),
                                      err_msg=f"round {r}")
        np.testing.assert_allclose(np.asarray(s_ref.stats.C),
                                   np.asarray(s_tb.stats.C),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["local", "mesh"])
def test_bound_families_parity_across_backends(blobs, backend):
    """Property: every bound family's labels AND centroids are bit-equal
    to ``bounds="none"`` on the same backend / init / schedule, with an
    N that is not a multiple of any shard count (pad/tail rows in the
    sharded path). The mesh leg shards over however many devices exist
    (1 in plain CI; the multi-device N % n_shards != 0 case runs in
    scripts/smoke_bounds.py); xl/multihost parity lives there too.
    """
    import jax

    from repro import api

    X, _ = blobs
    X = X[:1003]                      # odd N: never divides shard counts
    kw = {}
    if backend == "mesh":
        kw["mesh"] = jax.make_mesh((jax.device_count(), 1),
                                   ("data", "model"))
    base = None
    for fam in ["none", "hamerly2", "elkan", "exponion"]:
        cfg = api.FitConfig(k=8, algorithm="tb", b0=256, rho=np.inf,
                            bounds=fam, max_rounds=25, seed=0,
                            backend=backend)
        out = api.fit(X, cfg, **kw)
        if base is None:
            base = out
        else:
            np.testing.assert_array_equal(out.labels, base.labels,
                                          err_msg=f"{fam}/{backend}")
            np.testing.assert_array_equal(out.C, base.C,
                                          err_msg=f"{fam}/{backend}")


def test_exponion_annulus_boundary_tie():
    """An inter-centroid distance EXACTLY on the annulus boundary
    (d(c_a, c_j) == R) must not change the assignment or loosen the
    stored second-nearest bound.

    Geometry (f32-exact integer coordinates): anchor c0=(0,0) with
    x=(1,0) so u=1; s(0)=d(c0,c1)=3 via c1=(0,3); R = 2u+s = 5 equals
    d(c0,c2) = d(c0,c3) = 5 exactly for c2=(5,0), c3=(-5,0). The
    lower bound is manually deflated to force a Hamerly failure, so the
    point really scans its annulus.
    """
    import dataclasses as dc

    from repro.core.state import build_exponion_geom

    C = jnp.asarray([[0.0, 0.0], [0.0, 3.0], [5.0, 0.0], [-5.0, 0.0]])
    x = jnp.asarray([[1.0, 0.0]])
    state = init_state(x, 4, bounds="exponion")
    state = dc.replace(
        state,
        stats=dc.replace(state.stats, C=C,
                         p=jnp.zeros(4, jnp.float32)),
        points=dc.replace(state.points,
                          a=jnp.asarray([0], jnp.int32),
                          d=jnp.asarray([1.0], jnp.float32),
                          lb=jnp.asarray([0.5], jnp.float32)))
    geom = build_exponion_geom(C)
    # both boundary centroids are INSIDE the candidate set (<= count)
    assert float(geom.s[0]) == 3.0
    a, d, lb, n_rec, overflow, _ = rounds._assign_exponion(
        x, state, state.points.a, None, use_shalf=False)
    assert int(a[0]) == 0                      # assignment unchanged
    assert float(d[0]) == pytest.approx(1.0)
    # lb is the EXACT second-nearest (c1 at sqrt(10)), proving the
    # candidate set contained the true runner-up despite the ties
    assert float(lb[0]) == pytest.approx(np.sqrt(10.0), rel=1e-6)
    # all 4 centroids scanned (boundary pair included) + 1 d_a refresh
    assert int(n_rec) == 5
    assert not bool(overflow)


def test_capacity_compaction_is_exact(blobs):
    """Pruned rounds with a small capacity == dense rounds (after the
    driver's overflow retry)."""
    X, _ = blobs
    k, b = 8, 1024
    Xd = jnp.asarray(X)
    s_a = init_state(Xd, k, bounds="none")
    s_b = init_state(Xd, k, bounds="hamerly2")
    cap = None
    for r in range(10):
        s_a, _ = rounds.nested_round(Xd, s_a, b=b, rho=np.inf,
                                     bounds="none")
        while True:
            s_b2, info = rounds.nested_round(Xd, s_b, b=b, rho=np.inf,
                                             bounds="hamerly2",
                                             capacity=cap)
            if not bool(info.overflow):
                break
            cap = None if cap is None or 2 * cap >= b else 2 * cap
        s_b = s_b2
        cap = 256   # deliberately small -> exercises retry next round
        np.testing.assert_array_equal(np.asarray(s_a.points.a[:b]),
                                      np.asarray(s_b.points.a[:b]))


@pytest.mark.parametrize("capacity", [512, 1016])
@pytest.mark.parametrize("backend,tol", [("ref", 1e-6), ("pallas", 1e-5)])
def test_compacted_delta_matches_full_prefix(blobs, backend, tol, capacity):
    """A compacted hamerly2 round takes its delta S/v over the capacity
    buffer alone; it must equal the delta over the whole prefix. The
    prefix ends in pad rows (``n_valid < b``), and settled rows fill the
    buffer after the rows that need a rescan: at capacity 1016 the pads
    fill its tail too. The labels are ``bounds="none"``'s."""
    from repro.core.state import centroid_update
    from repro.kernels.plan import resolve_plan

    X, _ = blobs
    k, b, n_valid = 8, 1024, jnp.int32(1000)
    Xd = jnp.asarray(X)
    plan = resolve_plan(backend, b=b, k=k, d=X.shape[1], bounds="hamerly2")
    s = init_state(Xd, k, bounds="hamerly2")
    for _ in range(3):
        s, _ = rounds.nested_round(Xd, s, b=b, rho=np.inf, bounds="hamerly2",
                                   plan=plan, n_valid=n_valid)
    s_c, info = rounds.nested_round(Xd, s, b=b, rho=np.inf,
                                    bounds="hamerly2", capacity=capacity,
                                    plan=plan, n_valid=n_valid)
    assert not bool(info.overflow)
    assert 0 < int(info.n_recomputed) < capacity     # settled rows fill it
    assert int(info.n_changed) > 0

    s_none, _ = rounds.nested_round(Xd, s, b=b, rho=np.inf, bounds="none",
                                    plan=plan, n_valid=n_valid)
    np.testing.assert_array_equal(np.asarray(s_c.points.a[:b]),
                                  np.asarray(s_none.points.a[:b]))

    dS, dv = rounds._delta_sv(Xd[:b], s.points.a[:b], s_c.points.a[:b], k,
                              plan)
    want = centroid_update(dataclasses.replace(
        s.stats, S=s.stats.S + dS, v=s.stats.v + dv))
    for name in ("S", "v", "C"):
        np.testing.assert_allclose(np.asarray(getattr(s_c.stats, name)),
                                   np.asarray(getattr(want, name)),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("b,capacity,p", [
    (1024, 256, 0.1), (5000, 1024, 0.3), (1537, 1536, 0.9),
    (700, 64, 0.0), (700, 64, 1.0), (100_000, 16384, 0.05)])
def test_needs_first_matches_stable_argsort(b, capacity, p):
    """The sort-free compaction order is the stable argsort's, exactly:
    rescanned rows first, settled rows after, each in row order —
    across block boundaries (b % 512 != 0), and with none or all rows
    needing a rescan."""
    rng = np.random.default_rng(b + capacity)
    needs = rng.random(b) < p
    want = np.argsort(np.where(needs, 0, 1), kind="stable")[:capacity]
    got = rounds._needs_first(jnp.asarray(needs), capacity)
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# mb: S/v vectorised form == serial Alg. 1 oracle
# ---------------------------------------------------------------------------

def _serial_mb_round(X, idx, C, v):
    """Sculley's Algorithm 1, straight from the paper, in numpy."""
    C = C.copy()
    v = v.copy()
    a = {}
    for i in idx:                       # assignment step (C frozen)
        d = ((X[i] - C) ** 2).sum(1)
        a[i] = int(np.argmin(d))
    for i in idx:                       # update step (running mean)
        j = a[i]
        v[j] += 1
        eta = 1.0 / v[j]
        C[j] = (1 - eta) * C[j] + eta * X[i]
    return C, v


def test_mb_matches_serial_oracle(blobs):
    X, _ = blobs
    X = X[:600]
    k, b = 8, 100
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(X))
    Xs = X[perm]
    Xd = jnp.asarray(Xs)

    state = init_state(Xd, k, bounds="none")
    C_np = np.asarray(state.stats.C).copy()
    v_np = np.zeros(k)
    order = rng.permutation(len(X))
    for r in range(4):
        idx = order[r * b:(r + 1) * b]
        state, _ = rounds.mb_round(Xd, jnp.asarray(idx), state, fixed=False)
        C_np, v_np = _serial_mb_round(Xs, idx, C_np, v_np)
        np.testing.assert_allclose(np.asarray(state.stats.C), C_np,
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"round {r}")


def test_mbf_centroids_are_exact_current_means(blobs):
    """After any number of mb-f rounds: C(j) == mean of x(i) whose most
    recent assignment is j (the paper's contamination-removal claim)."""
    X, _ = blobs
    X = X[:1000]
    k, b = 8, 200
    Xd = jnp.asarray(X)
    state = init_state(Xd, k, bounds="none")
    rng = np.random.default_rng(1)
    for r in range(8):
        idx = rng.permutation(len(X))[:b]
        state, _ = rounds.mb_round(Xd, jnp.asarray(idx), state, fixed=True)
    a = np.asarray(state.points.a)
    C = np.asarray(state.stats.C)
    for j in range(k):
        members = X[a == j]
        if len(members):
            np.testing.assert_allclose(C[j], members.mean(0), rtol=1e-4,
                                       atol=1e-4)


# ---------------------------------------------------------------------------
# gb-inf with b0 = N == Lloyd
# ---------------------------------------------------------------------------

def test_nested_full_batch_equals_lloyd(blobs):
    X, _ = blobs
    k = 8
    r1 = _fit(X, k, algorithm="lloyd", seed=3)
    r2 = _fit(X, k, algorithm="gb", b0=len(X), rho=np.inf, seed=3)
    m1 = float(full_mse(jnp.asarray(X), jnp.asarray(r1.C)))
    m2 = float(full_mse(jnp.asarray(X), jnp.asarray(r2.C)))
    assert r1.converged and r2.converged
    assert abs(m1 - m2) / m1 < 1e-5


# ---------------------------------------------------------------------------
# end-to-end quality + paper's qualitative claims
# ---------------------------------------------------------------------------

def test_all_algorithms_reach_reasonable_quality(blobs, blobs_val):
    X, centers = blobs
    k = centers.shape[0]
    base = float(full_mse(jnp.asarray(blobs_val),
                          jnp.asarray(centers, jnp.float32)))
    for algo, kw in [("lloyd", {}), ("mb", dict(b0=256)),
                     ("mbf", dict(b0=256)),
                     ("gb", dict(b0=256)),
                     ("tb", dict(b0=256, bounds="hamerly2")),
                     ("tb", dict(b0=256, bounds="elkan")),
                     ("tb", dict(b0=256, bounds="exponion"))]:
        res = driver.fit(X, k, algorithm=algo, max_rounds=60, seed=0, **kw)
        mse = float(full_mse(jnp.asarray(blobs_val), jnp.asarray(res.C)))
        assert mse < 2.5 * base, (algo, mse, base)


def test_turbo_pruning_kicks_in(blobs):
    """tb-inf: once converged at b=N, the bound test eliminates all
    distance work (n_recomputed -> 0) — the turbocharging effect."""
    X, _ = blobs
    res = driver.fit(X, 8, algorithm="tb", b0=512, bounds="hamerly2",
                     max_rounds=60, seed=0)
    assert res.converged
    assert res.telemetry[-1]["n_recomputed"] == 0
    # and pruning was already substantial before full convergence
    assert res.telemetry[-3]["n_recomputed"] < 0.05 * len(X)


def test_batch_growth_is_nested_and_monotone(blobs):
    X, _ = blobs
    res = driver.fit(X, 8, algorithm="gb", b0=128, max_rounds=60, seed=0)
    bs = [t["b"] for t in res.telemetry if t["b"]]
    assert all(b2 >= b1 for b1, b2 in zip(bs, bs[1:]))
    assert bs[-1] == len(X)          # reached the full dataset
    assert bs[0] == 128


def test_lloyd_elkan_equals_lloyd(blobs):
    """The Elkan-accelerated Lloyd (nested engine at b0=N with faithful
    per-(i,j) bounds) reaches the identical local minimum."""
    X, _ = blobs
    r1 = _fit(X, 8, algorithm="lloyd", seed=5)
    r2 = _fit(X, 8, algorithm="lloyd-elkan", seed=5, max_rounds=60)
    m1 = float(full_mse(jnp.asarray(X), jnp.asarray(r1.C)))
    m2 = float(full_mse(jnp.asarray(X), jnp.asarray(r2.C)))
    assert r1.converged and r2.converged
    assert abs(m1 - m2) / m1 < 1e-5


def test_sgd_is_mb_with_batch_one(blobs):
    X, _ = blobs
    res = driver.fit(X[:500], 4, algorithm="sgd", max_rounds=200, seed=0)
    assert all(t["b"] == 1 for t in res.telemetry)
    mse0 = res.telemetry[0]["batch_mse"]
    # single-point rounds still drive centroids somewhere sensible
    mse = float(full_mse(jnp.asarray(X[:500]), jnp.asarray(res.C)))
    assert np.isfinite(mse)


# ---------------------------------------------------------------------------
# growth controller: sigma_C exact for small-count clusters
# ---------------------------------------------------------------------------

def test_sigma_c_exact_for_small_counts():
    """sigma_C = sqrt(sse / (v(v-1))) must use the TRUE denominator for
    1 < v < 2: the old maximum(denom, 1.0) clamp silently deflated the
    noise estimate of exactly the small clusters the paper's balancing
    argument cares about (v=1.5 -> denom 0.75, clamped to 1.0)."""
    from repro.core import controller

    sse = jnp.asarray([3.0, 3.0, 3.0, 8.0])
    v = jnp.asarray([1.5, 1.0, 0.0, 4.0])
    sig = np.asarray(controller.sigma_c(sse, v))
    # v=1.5: sqrt(3 / (1.5 * 0.5)) = 2.0 exactly — NOT sqrt(3) ~ 1.732
    assert sig[0] == pytest.approx(2.0)
    assert np.isinf(sig[1]) and np.isinf(sig[2])     # v <= 1: undefined
    assert sig[3] == pytest.approx(np.sqrt(8.0 / 12.0))
    # the deflation changed growth votes: a cluster with v=1.5 and p just
    # above the clamped estimate must now vote grow at rho=1
    p = jnp.asarray([1.9, 1.0, 1.0, 1.0])
    ratios = np.asarray(controller.growth_ratios(sse, v, p))
    assert ratios[0] > 1.0                  # exact: 2.0/1.9 > 1
    assert np.sqrt(3.0) / 1.9 < 1.0         # clamped estimate would not
