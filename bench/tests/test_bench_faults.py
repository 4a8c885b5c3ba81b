"""A run with the timed path broken underneath comes out not correct.

Each case plants one fault in the program (in this process only), then
drives the rest of a benchmark run on the CPU at a tiny size, past the
look for a chip, and reads ``correct`` from the result. The sound runs
come out correct, so each fault is what the check caught.
"""
import jax
import jax.numpy as jnp
import pytest

import bench_tiny


def _step_returns_state_unchanged(mp):
    from repro.api.engines import local
    orig = local.nested_jit

    def frozen(X, state, **kw):
        return state, orig(X, state, **kw)[1]
    mp.setattr(local, "nested_jit", frozen)


def _half_the_rows_left_out_of_the_sums(mp):
    from repro.kernels import ops
    orig = ops.cluster_sum

    def half(x, a, k, *, weights=None, **kw):
        n = x.shape[0]
        w = jnp.ones((n,), jnp.float32) if weights is None else weights
        return orig(x, a, k, weights=w * (jnp.arange(n) % 2 == 0), **kw)
    mp.setattr(ops, "cluster_sum", half)


def _a_fit_label_altered(mp):
    from repro.api import estimator
    orig = estimator.run_loop

    def altered(run, config, **kw):
        out = orig(run, config, **kw)
        out.labels = out.labels.copy()
        out.labels[0] = (out.labels[0] + 1) % config.k
        return out
    mp.setattr(estimator, "run_loop", altered)


def _served_label_altered(mp):
    from repro.serve import snapshot
    orig = snapshot._predict_jit

    def altered(X, C, **kw):
        a, d1 = orig(X, C, **kw)
        return a.at[0].set((a[0] + 1) % C.shape[0]), d1
    mp.setattr(snapshot, "_predict_jit", altered)


def _half_the_request_left_out(mp):
    from repro.serve import snapshot
    orig = snapshot._predict_jit

    def half(X, C, **kw):
        a, d1 = orig(X, C, **kw)
        keep = jnp.arange(a.shape[0]) < (a.shape[0] + 1) // 2
        return jnp.where(keep, a, 0), d1
    mp.setattr(snapshot, "_predict_jit", half)


def _served_output_never_written(mp):
    from repro.serve import snapshot
    orig = snapshot._predict_jit

    def stale(X, C, **kw):
        a, d1 = orig(X, C, **kw)
        return jnp.zeros_like(a), d1
    mp.setattr(snapshot, "_predict_jit", stale)


CASES = {
    "fit.sound": ("infmnist_k50.fit", None),
    "fit.step_returns_state_unchanged": (
        "infmnist_k50.fit", _step_returns_state_unchanged),
    "fit.half_the_rows_left_out_of_the_sums": (
        "infmnist_k50.fit", _half_the_rows_left_out_of_the_sums),
    "fit.label_altered": ("infmnist_k50.fit", _a_fit_label_altered),
    "predict.sound": ("infmnist_k50.predict", None),
    "predict.label_altered": ("infmnist_k50.predict",
                              _served_label_altered),
    "predict.half_the_request_left_out": (
        "infmnist_k50.predict", _half_the_request_left_out),
    "predict.output_never_written": ("infmnist_k50.predict",
                                     _served_output_never_written),
}


@pytest.mark.parametrize("case", list(CASES))
def test_fault_is_caught(case, tmp_path, monkeypatch):
    workload, plant = CASES[case]
    root = bench_tiny.tiny_root(tmp_path)
    jax.clear_caches()        # no executable traced before the fault
    if plant is not None:
        plant(monkeypatch)
    try:
        res = bench_tiny.run_cell(root, workload, seed=2**31 + 11,
                                  seconds=0.5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["attempted"] > 0
    if plant is None:
        assert res["correct"] is True and res["failed"] == 0
    else:
        assert res["correct"] is False
        assert res["failed"] > 0
