"""Loop-aware HLO cost model: validated against known programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis as ra
from repro.roofline import hlo_cost


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops():
    n, k, m = 256, 512, 128

    def f(a, b):
        return a @ b

    txt = _compiled_text(f, jax.ShapeDtypeStruct((n, k), jnp.float32),
                         jax.ShapeDtypeStruct((k, m), jnp.float32))
    c = hlo_cost.analyze(txt)
    expect = 2.0 * n * k * m
    assert 0.9 * expect <= c.flops <= 1.2 * expect, c.flops


def test_scan_multiplies_flops_by_trip_count():
    n, trips = 128, 20

    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=trips)
        return h

    txt = _compiled_text(f, jax.ShapeDtypeStruct((4, n), jnp.float32),
                         jax.ShapeDtypeStruct((n, n), jnp.float32))
    c = hlo_cost.analyze(txt)
    expect = trips * 2.0 * 4 * n * n
    assert 0.9 * expect <= c.flops <= 1.5 * expect, (c.flops, expect)


def test_nested_scan_trip_product():
    n, t1, t2 = 64, 5, 7

    def f(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=t2)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=t1)
        return h

    txt = _compiled_text(f, jax.ShapeDtypeStruct((4, n), jnp.float32),
                         jax.ShapeDtypeStruct((n, n), jnp.float32))
    c = hlo_cost.analyze(txt)
    expect = t1 * t2 * 2.0 * 4 * n * n
    assert 0.8 * expect <= c.flops <= 1.6 * expect, (c.flops, expect)


def test_collective_parse_crafted_hlo():
    txt = """
HloModule test
ENTRY %main (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  %ag = f32[128,16]{1,0} all-gather(%p), dimensions={0}
  %ar = f32[16]{0} all-reduce(%p), to_apply=%add
  ROOT %cp = f32[16]{0} collective-permute(%p), source_target_pairs={{0,1}}
}
"""
    stats = ra.parse_collectives(txt)
    assert stats.counts == {"all-gather": 1, "all-reduce": 1,
                            "collective-permute": 1}
    assert stats.by_kind["all-gather"] == 128 * 16 * 4
    assert stats.by_kind["all-reduce"] == 2 * 16 * 4
    assert stats.by_kind["collective-permute"] == 16 * 4


def test_roofline_terms_and_bottleneck():
    r = ra.roofline_terms(197e12, 819e9 * 2, 50e9 * 0.5,
                          peaks=ra.PEAKS[ra.V5E], model_flops=98.5e12)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.useful_ratio == pytest.approx(0.5)
    assert r.roofline_fraction() == pytest.approx(0.25)


def test_dus_stack_counts_slice_not_buffer():
    """Writing one layer's slice into a big stacked buffer inside a scan
    must count slice bytes, not the whole stack, per iteration."""
    L_, S, D = 16, 64, 32

    def f(x, stack):
        def body(c, i):
            return c, jax.lax.dynamic_update_slice_in_dim(
                stack, (x * 1.0)[None], 0, axis=0)[i]
        _, ys = jax.lax.scan(body, 0.0, jnp.arange(L_))
        return ys

    txt = _compiled_text(f, jax.ShapeDtypeStruct((S, D), jnp.float32),
                         jax.ShapeDtypeStruct((L_, S, D), jnp.float32))
    c = hlo_cost.analyze(txt)
    stack_bytes = L_ * S * D * 4
    # far below trips x full-stack traffic
    assert c.bytes < 0.5 * L_ * 3 * stack_bytes, c.bytes
