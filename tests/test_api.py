"""The unified `repro.api` surface: config, engines, estimator.

Key guarantees:
  * FitConfig validates and round-trips through JSON-safe dicts;
  * NestedKMeans.fit == legacy driver.fit BIT-IDENTICALLY (centroids
    and telemetry) — the refactor moved the loop, not the math;
  * partial_fit is exactly one nested_round on the streamed batch;
  * the shared loop serves every legacy algorithm alias.
"""
import dataclasses
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import driver, rounds
from repro.core.state import init_state


# ---------------------------------------------------------------------------
# FitConfig
# ---------------------------------------------------------------------------

def test_fitconfig_roundtrip_through_json():
    cfg = api.FitConfig(k=50, algorithm="tb", rho=math.inf, b0=2000,
                        bounds="hamerly2", time_budget_s=30.0, seed=3,
                        kernel_backend="ref", data_axes=("pod", "data"))
    wire = json.dumps(cfg.to_dict())      # must be strict JSON (inf-safe)
    assert "Infinity" not in wire
    back = api.FitConfig.from_dict(json.loads(wire))
    assert back == cfg
    assert back.rho == math.inf and back.data_axes == ("pod", "data")


def test_fitconfig_defaults_roundtrip():
    cfg = api.FitConfig(k=8)
    assert api.FitConfig.from_dict(cfg.to_dict()) == cfg


@pytest.mark.parametrize("bad", [
    dict(k=0),
    dict(k=8, algorithm="kmeans++"),
    dict(k=8, bounds="yinyang"),
    dict(k=8, b0=0),
    dict(k=8, rho=0.0),
    dict(k=8, eval_every=0),
    dict(k=8, kernel_backend="cuda"),
    dict(k=8, backend="tpu-pod"),
    dict(k=8, backend="mesh", algorithm="mb"),   # mesh is nested-only
    dict(k=8, backend="xl", algorithm="lloyd"),  # xl is nested-only
    dict(k=8, backend="multihost", algorithm="mbf"),
    dict(k=8, backend="xl", model_axis=""),      # needs a real axis name
    dict(k=8, backend="xl", data_axes=("model",),
         model_axis="model"),                    # axes must be disjoint
    # coordinator fields: all three together, and multihost-only
    dict(k=8, backend="multihost", coordinator_address="localhost:1"),
    dict(k=8, backend="mesh", coordinator_address="localhost:1",
         num_processes=2, process_id=0),
    dict(k=8, backend="multihost", coordinator_address="localhost:1",
         num_processes=2, process_id=2),         # id out of range
])
def test_fitconfig_validation_rejects(bad):
    with pytest.raises(ValueError):
        api.FitConfig(**bad)


def test_fitconfig_xl_roundtrip():
    cfg = api.FitConfig(k=16, algorithm="tb", backend="xl",
                        data_axes=("pod", "data"), model_axis="mdl",
                        rho=100.0)
    back = api.FitConfig.from_dict(cfg.to_dict())
    assert back == cfg
    assert back.backend == "xl" and back.model_axis == "mdl"


def test_fitconfig_from_dict_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown"):
        api.FitConfig.from_dict({"k": 8, "banana": 1})


def test_fitconfig_resolve_aliases():
    n = 1000
    assert api.FitConfig(k=4, algorithm="sgd").resolve(n).b0 == 1
    le = api.FitConfig(k=4, algorithm="lloyd-elkan").resolve(n)
    assert (le.algorithm, le.b0, le.bounds) == ("tb", n, "elkan")
    gb = api.FitConfig(k=4, algorithm="gb").resolve(n)
    assert (gb.algorithm, gb.bounds) == ("tb", "none")
    assert api.FitConfig(k=4, algorithm="mb").resolve(n).bounds == "none"


# ---------------------------------------------------------------------------
# estimator vs legacy driver: bit-identical
# ---------------------------------------------------------------------------

def test_fit_bit_identical_to_legacy_driver(blobs, blobs_val):
    """tb-inf through NestedKMeans == driver.fit: same centroids bits,
    same telemetry stream."""
    X, _ = blobs
    k = 8
    legacy = driver.fit(X, k, algorithm="tb", rho=math.inf, b0=512,
                        bounds="hamerly2", X_val=blobs_val, max_rounds=40,
                        eval_every=5, seed=0)
    km = api.NestedKMeans(api.FitConfig(
        k=k, algorithm="tb", rho=math.inf, b0=512, bounds="hamerly2",
        max_rounds=40, eval_every=5, seed=0)).fit(X, X_val=blobs_val)
    np.testing.assert_array_equal(legacy.C, km.cluster_centers_)
    assert legacy.converged == km.converged_
    assert len(legacy.telemetry) == km.n_rounds_
    for old, new in zip(legacy.telemetry, km.telemetry_):
        d = new.to_dict()
        # t is wall-clock (jit compile lands in whichever runs first)
        assert {k: v for k, v in old.items() if k != "t"} \
            == {k: v for k, v in d.items() if k != "t"}


def test_fit_bit_identical_mb_and_lloyd(blobs):
    """The resampling stream (mb) and lloyd paths also moved intact."""
    X, _ = blobs
    for algo, kw in [("mb", dict(b0=256)), ("mbf", dict(b0=256)),
                     ("lloyd", {})]:
        legacy = driver.fit(X, 8, algorithm=algo, max_rounds=15, seed=2,
                            **kw)
        out = api.fit(X, api.FitConfig(k=8, algorithm=algo, max_rounds=15,
                                       seed=2, **kw))
        np.testing.assert_array_equal(legacy.C, out.C), algo


def test_callback_streams_telemetry(blobs):
    X, _ = blobs
    seen = []
    api.fit(X, api.FitConfig(k=8, b0=512, max_rounds=8, seed=0),
            on_round=seen.append)
    assert len(seen) == 8
    assert all(isinstance(r, api.Telemetry) for r in seen)
    assert [r.round for r in seen] == list(range(8))


# ---------------------------------------------------------------------------
# estimator inference surface
# ---------------------------------------------------------------------------

def test_predict_transform_score(blobs, blobs_val):
    X, centers = blobs
    k = centers.shape[0]
    km = api.NestedKMeans(api.FitConfig(k=k, b0=512, max_rounds=60,
                                        seed=0)).fit(X)
    a = km.predict(blobs_val)
    D = km.transform(blobs_val)
    assert a.shape == (len(blobs_val),) and D.shape == (len(blobs_val), k)
    # predict is argmin of transform
    np.testing.assert_array_equal(a, np.argmin(D, axis=1))
    # score == -sum of squared nearest distances
    np.testing.assert_allclose(-km.score(blobs_val),
                               (D.min(axis=1) ** 2).sum(), rtol=1e-4)


def test_unfitted_estimator_raises(blobs_val):
    km = api.NestedKMeans(api.FitConfig(k=4))
    with pytest.raises(api.NotFittedError):
        km.predict(blobs_val)


def test_labels_are_in_caller_row_order(blobs):
    """The engines shuffle internally; labels_ must come back in the
    caller's row order (== predict with the final centroids once
    converged)."""
    X, _ = blobs
    km = api.NestedKMeans(api.FitConfig(k=8, b0=512, max_rounds=80,
                                        seed=0)).fit(X)
    assert km.converged_
    labels = km.labels_
    assert labels.shape == (len(X),) and labels.min() >= 0
    np.testing.assert_array_equal(labels, km.predict(X))


def test_legacy_algorithms_list_matches_api():
    assert driver.ALGORITHMS == api.ALGORITHMS


def test_partial_fit_runs_sharded(blobs):
    """partial_fit streams through the configured engine (the old
    local-only restriction is gone): a mesh-backed stream on a trivial
    1-device mesh matches the local stream after a shared fit."""
    import jax
    X, _ = blobs
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    km_l = api.NestedKMeans(api.FitConfig(k=8, b0=512, seed=0))
    km_m = api.NestedKMeans(api.FitConfig(k=8, b0=512, seed=0,
                                          backend="mesh"), mesh=mesh)
    km_l.fit(X[:2048])
    km_m.fit(X[:2048])
    for i in range(2):
        batch = X[2048 + i * 500:2048 + (i + 1) * 500]
        km_l.partial_fit(batch)
        km_m.partial_fit(batch)
    assert km_m.counts_.sum() == km_l.counts_.sum()
    assert km_m.telemetry_[-1].b == 500
    np.testing.assert_allclose(km_l.cluster_centers_,
                               km_m.cluster_centers_, atol=1e-3)


# ---------------------------------------------------------------------------
# partial_fit: the streaming primitive
# ---------------------------------------------------------------------------

def test_partial_fit_is_one_nested_round(blobs):
    """partial_fit on a fitted estimator == one nested_round whose stats
    are the estimator's and whose points are the fresh batch."""
    X, _ = blobs
    k = 8
    km = api.NestedKMeans(api.FitConfig(k=k, b0=512, max_rounds=30,
                                        seed=0)).fit(X[:2048])
    batch = X[2048:2048 + 256]

    # oracle: the same round by hand
    Xd = jnp.asarray(batch)
    state = init_state(Xd, k, bounds="hamerly2")
    state = dataclasses.replace(state, stats=km.outcome_.state.stats)
    want, want_info = rounds.nested_round(
        Xd, state, b=256, rho=math.inf, bounds="hamerly2", capacity=None,
        use_shalf=True)

    n_before = km.n_rounds_
    km.partial_fit(batch)
    np.testing.assert_array_equal(np.asarray(want.stats.C),
                                  km.cluster_centers_)
    rec = km.telemetry_[-1]
    assert km.n_rounds_ == n_before + 1
    assert rec.b == 256
    assert rec.n_changed == int(want_info.n_changed)
    assert rec.batch_mse == pytest.approx(float(want_info.batch_mse))


def test_partial_fit_from_scratch_then_stream(blobs):
    """partial_fit bootstraps without fit() and keeps absorbing batches."""
    X, _ = blobs
    km = api.NestedKMeans(api.FitConfig(k=8))
    for i in range(4):
        km.partial_fit(X[i * 512:(i + 1) * 512])
    assert km.n_rounds_ == 4
    assert km.cluster_centers_.shape == (8, X.shape[1])
    # all four batches are in the running statistics
    assert km.counts_.sum() == pytest.approx(4 * 512)
    a = km.predict(X[:512])
    assert a.min() >= 0 and a.max() < 8


def test_partial_fit_first_batch_must_cover_k():
    with pytest.raises(ValueError, match=">= k"):
        api.NestedKMeans(api.FitConfig(k=64)).partial_fit(
            np.zeros((8, 4), np.float32))


def test_partial_fit_after_fit_staleness_contract(blobs):
    """partial_fit moves the centroids past the fit's outcome, so the
    fit-scoped attributes (labels_/outcome_) raise NotFittedError
    instead of silently serving stale assignments; the live surface
    (centers, predict, telemetry) keeps working."""
    X, _ = blobs
    km = api.NestedKMeans(api.FitConfig(k=8, b0=512, max_rounds=30,
                                        seed=0)).fit(X[:2048])
    _ = km.labels_            # fresh after fit
    _ = km.outcome_
    km.partial_fit(X[2048:2048 + 256])
    with pytest.raises(api.NotFittedError, match="stale"):
        _ = km.labels_
    with pytest.raises(api.NotFittedError, match="stale"):
        _ = km.outcome_
    # the streaming surface stays live
    assert km.cluster_centers_.shape == (8, X.shape[1])
    assert km.predict(X[:64]).shape == (64,)
    # a fresh fit() clears the staleness
    km.fit(X[:2048])
    assert km.labels_.shape == (2048,)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def test_make_engine_selects_backend():
    assert isinstance(api.make_engine(api.FitConfig(k=4)),
                      api.LocalEngine)
    with pytest.raises(ValueError, match="mesh"):
        api.make_engine(api.FitConfig(k=4, backend="mesh"))
    with pytest.raises(ValueError, match="Mesh"):
        api.make_engine(api.FitConfig(k=4, backend="xl"))
    # multihost builds its own mesh lazily (at begin) when none given
    assert isinstance(api.make_engine(api.FitConfig(k=4,
                                                    backend="multihost")),
                      api.MultiHostEngine)


def test_fitconfig_multihost_roundtrip():
    cfg = api.FitConfig(k=8, backend="multihost",
                        coordinator_address="localhost:1234",
                        num_processes=2, process_id=1)
    back = api.FitConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    assert (back.coordinator_address, back.num_processes,
            back.process_id) == ("localhost:1234", 2, 1)


def test_multihost_single_device_matches_mesh(blobs):
    """backend="multihost" with one process and one device is the mesh
    engine bit for bit (the multi-device / multi-process face of this
    parity chain lives in scripts/smoke_multihost.py)."""
    import jax
    X, _ = blobs
    cfg = api.FitConfig(k=8, b0=512, max_rounds=40, seed=0)
    mesh = jax.make_mesh((1,), ("data",))
    out_m = api.fit(X, dataclasses.replace(cfg, backend="mesh"),
                    mesh=mesh)
    out_h = api.fit(X, dataclasses.replace(cfg, backend="multihost"))
    assert out_m.converged and out_h.converged
    np.testing.assert_array_equal(out_m.C, out_h.C)
    np.testing.assert_array_equal(out_m.labels, out_h.labels)
    for ra, rb in zip(out_m.telemetry, out_h.telemetry):
        da, db = ra.to_dict(), rb.to_dict()
        da.pop("t"), db.pop("t")
        assert da == db


def test_xl_engine_begin_on_trivial_mesh():
    """XLEngine.begin stands up the sharded layout on a 1x1 mesh (the
    k % model-axis divisibility error needs forced multi-device hosts
    and is covered by the smoke in tests/test_distributed_xl.py)."""
    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    run = api.XLEngine(mesh).begin(
        np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32),
        api.FitConfig(k=4, backend="xl").resolve(64))
    assert run.n_shards == 1 and run.n_points == 64
    assert run.state.stats.C.shape == (4, 8)


def test_run_loop_time_budget_zero(blobs):
    X, _ = blobs
    out = api.fit(X, api.FitConfig(k=8, time_budget_s=0.0))
    assert out.telemetry == [] and not out.converged


def test_outcome_carries_config(blobs):
    X, _ = blobs
    cfg = api.FitConfig(k=8, algorithm="gb", b0=256, max_rounds=10)
    out = api.fit(X, cfg)
    # outcome records the RESOLVED config (canonical algorithm)
    assert out.config.algorithm == "tb" and out.config.bounds == "none"
    assert out.config.k == 8


# ---------------------------------------------------------------------------
# the in-memory shuffle: gathered on the device, or on the host when the
# device has too little memory free for the gather
# ---------------------------------------------------------------------------

def _unshuffled_twin(X, cfg):
    """`api.fit` on ``X`` and on ``X`` shuffled by the fit's own
    permutation with the fit's shuffle off: (outcome, twin, perm)."""
    perm = np.random.default_rng(cfg.seed).permutation(len(X))
    twin = api.fit(X[perm], dataclasses.replace(cfg, shuffle=False))
    return api.fit(X, cfg), twin, perm


def _telemetry_without_t(out):
    return [{k: v for k, v in r.to_dict().items() if k != "t"}
            for r in out.telemetry]


@pytest.mark.parametrize("algorithm,bounds",
                         [("tb", "hamerly2"), ("gb", "none")])
def test_shuffled_fit_bit_equal_to_prepermuted_rows(blobs, algorithm,
                                                    bounds):
    """The device gather lays the rows out exactly as ``X[perm]``."""
    X, _ = blobs
    cfg = api.FitConfig(k=8, algorithm=algorithm, bounds=bounds, b0=256,
                        max_rounds=60, seed=5)
    out, twin, perm = _unshuffled_twin(X, cfg)
    np.testing.assert_array_equal(out.C, twin.C)
    np.testing.assert_array_equal(out.labels[perm], twin.labels)
    assert _telemetry_without_t(out) == _telemetry_without_t(twin)


ROWS_BYTES = 4000 * 16 * 4


@pytest.mark.parametrize("need,stats,path", [
    (2 * ROWS_BYTES, {"bytes_limit": 3 * ROWS_BYTES, "bytes_in_use": 0},
     "device"),
    (2 * ROWS_BYTES, {"bytes_limit": 3 * ROWS_BYTES,
                      "bytes_in_use": 2 * ROWS_BYTES}, "host"),
    (2 * ROWS_BYTES, {"bytes_limit": ROWS_BYTES}, "host"),
    (2 * ROWS_BYTES, {"bytes_limit": 2 * ROWS_BYTES, "bytes_in_use": 0},
     "device"),
    (2 * ROWS_BYTES, None, "device"),
    (2 * ROWS_BYTES, {}, "device"),
])
def test_gather_path_decision(need, stats, path):
    from repro.api.engines.local import gather_path
    assert gather_path(need, stats) == path


@pytest.mark.parametrize("limit,path", [(1024, "host"), (1 << 40, "device")])
def test_gather_path_from_memory_stats(blobs, monkeypatch, tmp_path, limit,
                                       path):
    """A device that reports its memory decides by the gather's own
    compiled footprint; either branch fits bit-equal to the CPU's
    (no statistics: device), and the trace names the branch taken."""
    from repro.api.engines import local
    from repro.obs import read_events
    X, _ = blobs
    cfg = api.FitConfig(k=8, b0=256, max_rounds=30, seed=3)
    ref = api.fit(X, cfg)
    monkeypatch.setattr(local, "_memory_stats",
                        lambda: {"bytes_limit": limit, "bytes_in_use": 0})
    out = api.fit(X, dataclasses.replace(cfg, trace_dir=str(tmp_path)))
    np.testing.assert_array_equal(out.C, ref.C)
    np.testing.assert_array_equal(out.labels, ref.labels)
    assert _telemetry_without_t(out) == _telemetry_without_t(ref)
    to_device, = [e for e in read_events(tmp_path)
                  if e.get("name") == "fit.to_device"]
    assert to_device["attrs"] == {"gather": path}


def test_gather_bytes_counts_both_copies():
    from repro.api.engines.local import _gather_bytes
    X = np.zeros((1000, 16), np.float64)     # put as f32
    perm = np.arange(1000, dtype=np.int32)
    assert _gather_bytes(X, perm) >= 2 * 1000 * 16 * 4 + perm.nbytes


def test_second_fit_of_a_shape_compiles_nothing():
    """Every executable of an in-memory fit, the shuffle's gather among
    them, is keyed on shapes: a second fit of the same shape compiles
    nothing (the benchmark's ``compiles_in_window.fit`` reads 0)."""
    import jax.monitoring as mon
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    # a shape no other test fits, so the first fit compiles the gather
    X = np.random.default_rng(7).normal(size=(1531, 12)).astype(np.float32)
    cfg = api.FitConfig(k=6, b0=128, max_rounds=25, seed=1)
    mon.register_event_duration_secs_listener(listen)
    try:
        first = api.fit(X, cfg)
        n_first = len(compiles)
        second = api.fit(X, dataclasses.replace(cfg, seed=2))
    finally:
        mon.unregister_event_duration_listener(listen)
    assert n_first > 0
    assert len(compiles) == n_first
    assert first.telemetry and second.telemetry
