"""Compile the main path's kernels for a described TPU v5e, without one.

The Pallas kernels run in interpret mode everywhere else in the suite,
which accepts tiles and VMEM footprints the chip's compiler refuses.
Here the real compiler lowers them at the infMNIST width (d=784) and a
65,536-row batch, with the blocks the default plan picks, for the paper's
k=50 and the over-segmented k=1024, a compacted nested round at k=50, and
the predict path at three request sizes. Nothing runs: a pass says the
chip's compiler accepts the program.

The topology is described inside a fixture, never at import time, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import pytest

B, D = 65536, 784


@pytest.fixture(scope="module")
def one_chip():
    """A single-device sharding on a described v5e chip, with JAX's
    persistent compilation cache off: a compile for a device that is not
    attached is written to it but cannot be read back. On the way out
    the in-memory caches are cleared too: the predict trace made while
    ``jax.default_backend`` reads "tpu" holds a compiled-mode Pallas call,
    and a later call of the same shapes on the CPU would reuse it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tpu_plan(k):
    from repro.kernels.plan import resolve_plan
    return resolve_plan("pallas", b=B, k=k, d=D, platform="tpu",
                        tune=False, bounds="hamerly2")


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [50, 1024])
@pytest.mark.parametrize("kernel", ["fused_nested_round", "assign_top2",
                                    "cluster_sum"])
def test_kernel_compiles_for_v5e(one_chip, kernel, k):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    plan = _tpu_plan(k)
    assert not plan.interpret and plan.source == "table"
    x = _spec((B, D), jnp.float32, one_chip)
    c = _spec((k, D), jnp.float32, one_chip)
    rows_i = _spec((B,), jnp.int32, one_chip)
    rows_f = _spec((B,), jnp.float32, one_chip)
    rows_b = _spec((B,), jnp.bool_, one_chip)
    if kernel == "fused_nested_round":
        fn = jax.jit(lambda *a: ops.fused_nested_round(*a, plan=plan))
        args = (x, c, rows_i, rows_b, rows_f, rows_f, rows_b)
    elif kernel == "assign_top2":
        fn = jax.jit(lambda x, c: ops.assign_top2(x, c, plan=plan))
        args = (x, c)
    else:
        fn = jax.jit(lambda x, a, w: ops.cluster_sum(x, a, k, weights=w,
                                                     plan=plan))
        args = (x, rows_i, rows_f)
    _assert_mosaic(fn.lower(*args).compile())


def test_compacted_round_pads_no_whole_prefix(one_chip):
    """A compacted hamerly2 round (capacity 8,192 of a 65,536-row
    prefix, k=50) takes its delta S/v over the capacity buffer, so the
    chip's program holds no copy of the whole prefix padded to the
    cluster-sum kernel's 1,024 features."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api.engines.local import nested_jit
    from repro.core.state import init_state

    k = 50
    x = _spec((B, D), jnp.float32, one_chip)
    state = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda X: init_state(X, k, bounds="hamerly2"), x))
    compiled = nested_jit.lower(
        x, state, b=B, rho=np.inf, bounds="hamerly2", capacity=8192,
        plan=_tpu_plan(k)).compile()
    _assert_mosaic(compiled)
    assert f"f32[{B},1024]" not in compiled.as_text()


@pytest.mark.parametrize("rows", [1, 256, 2048])
def test_predict_compiles_for_v5e(one_chip, rows, monkeypatch):
    """`CodebookSnapshot.predict`'s jitted body, as the chip resolves it:
    the auto backend picks pallas there, so the test makes the plan see a
    TPU platform."""
    import jax
    import jax.numpy as jnp

    from repro.serve.snapshot import _predict_jit

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _spec((rows, D), jnp.float32, one_chip)
    c = _spec((50, D), jnp.float32, one_chip)
    _assert_mosaic(_predict_jit.lower(x, c, backend=None).compile())
