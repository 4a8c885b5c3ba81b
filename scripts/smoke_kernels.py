"""Kernel dispatch plane end-to-end check (run via tests/test_kernels.py).

Gates the PR 9 dispatch refactor with 8 forced host devices:

  1. op parity — the fused nested-round Pallas kernel (interpret mode
     off-TPU) matches the jnp oracle at an awkward shape: labels exact,
     floats close;
  2. fit parity, local — full `run_loop` fits with
     ``kernel_backend="pallas"`` are bit-identical in labels to
     ``kernel_backend="ref"`` for both bound families (tb/hamerly2
     rides the fused kernel, gb/none the bound-free variant), and the
     outcome surfaces the resolved `KernelPlan`;
  3. fit parity, XL — same bit-parity on a (4 data, 2 model) mesh
     (m=2: per-op Pallas kernels through the plan) and on (8, 1)
     (m=1: the fused round, model-axis collectives are identity);
  4. auditors stay green with the plan active — retrace (local + xl)
     proves the plan is a constant static (one trace per (b, capacity)
     bucket, nothing else keys the jit cache) and hostsync proves the
     fused dispatch adds no device->host syncs.
"""
from repro.util.env import force_host_device_count
force_host_device_count(8)

import dataclasses

import jax
import numpy as np

from repro import api
from repro.analysis import hostsync, retrace
from repro.kernels.fused_round import (fused_nested_round_pallas,
                                       fused_nested_round_ref)


def blobs(n, k, d, seed=0):
    """Well-separated blobs: inter-center distance dwarfs float32 ulp
    drift in the S->C reduction, so correct kernels give BIT-equal
    labels, not merely close ones."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 12.0
    a = rng.integers(0, k, size=n)
    return (centers[a] + rng.normal(size=(n, d))).astype(np.float32)


# -- 1. fused kernel vs the jnp oracle at an awkward shape ------------------
rng = np.random.default_rng(1)
n, k, d = 300, 48, 7                       # n % bn != 0, k % 128 != 0
x = rng.normal(size=(n, d)).astype(np.float32)
c = rng.normal(size=(k, d)).astype(np.float32)
a_prev = rng.integers(-1, k, size=n).astype(np.int32)
settled = rng.random(n) < 0.3
d_keep = rng.random(n).astype(np.float32)
lb_keep = rng.random(n).astype(np.float32)
valid = rng.random(n) < 0.9
args = (x, c, a_prev, settled, d_keep, lb_keep, valid)
outs_p = fused_nested_round_pallas(*args, bn=128, interpret=True)
outs_r = fused_nested_round_ref(*args)
np.testing.assert_array_equal(np.asarray(outs_p[0]), np.asarray(outs_r[0]))
for op, orf, name in zip(outs_p[1:], outs_r[1:],
                         ("d", "lb", "S", "v", "sse")):
    np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                               atol=2e-5, rtol=2e-5, err_msg=name)
print("op parity: fused nested round == oracle at (300, 48, 7)")


# -- 2. full fits, local: pallas labels bit-equal to ref --------------------
def fit_pair(cfg, X, mesh=None):
    out_r = api.fit(X, dataclasses.replace(cfg, kernel_backend="ref"),
                    mesh=mesh)
    out_p = api.fit(X, dataclasses.replace(cfg, kernel_backend="pallas"),
                    mesh=mesh)
    np.testing.assert_array_equal(out_p.labels, out_r.labels)
    assert len(out_p.telemetry) == len(out_r.telemetry)
    assert (out_p.kernel_plan or {}).get("backend") == "pallas", \
        out_p.kernel_plan
    return out_p


X = blobs(2048, 16, 8)
cfg = api.FitConfig(k=16, algorithm="tb", b0=256, max_rounds=60, seed=0,
                    capacity_floor=64)
out = fit_pair(cfg, X)
print(f"local tb (fused hamerly2): labels bit-equal over "
      f"{len(out.telemetry)} rounds, plan={out.kernel_plan['backend']}"
      f"/bn={out.kernel_plan['bn']}")

Xg = blobs(1536, 9, 12, seed=2)
fit_pair(api.FitConfig(k=9, algorithm="gb", b0=100, max_rounds=60,
                       seed=0), Xg)
print("local gb (fused bounds-free): labels bit-equal")

# -- 3. full fits, XL: m=2 (per-op kernels) and m=1 (fused round) ----------
cfg_xl = api.FitConfig(k=16, algorithm="tb", b0=256, max_rounds=60,
                       seed=0, backend="xl", data_axes=("data",),
                       model_axis="model", capacity_floor=64)
fit_pair(cfg_xl, X, mesh=jax.make_mesh((4, 2), ("data", "model")))
print("xl (4,2) m=2: labels bit-equal")
fit_pair(cfg_xl, X, mesh=jax.make_mesh((8, 1), ("data", "model")))
print("xl (8,1) m=1 (fused): labels bit-equal")

# -- 4. auditors with the plan active --------------------------------------
for backend in ("local", "xl"):
    v = retrace.audit_backend(backend, kernel_backend="pallas")
    assert not v, [str(x) for x in v]
    print(f"retrace[{backend}] with pallas plan: one trace per bucket")
v = hostsync.audit_backend("local", kernel_backend="pallas")
assert not v, [str(x) for x in v]
print("hostsync[local] with pallas plan: zero unsanctioned syncs")

print("kernels smoke OK")
