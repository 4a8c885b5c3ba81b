"""XL-engine end-to-end check (run via tests/test_distributed_xl.py).

Promoted from the one-shot round smoke: the centroid-sharded path is
now loop-driven by `repro.api.engines.xl.XLEngine`, and this script gates
the whole stack with 8 forced host devices:

  1. round oracle — `make_xl_round` + `make_dp_round` match one exact
     Lloyd-style update from the same centroids;
  2. sharded top-2 fold parity — `assign_top2_sharded`'s log-depth tree
     fold matches single-device `ops.assign_top2` bit for bit,
     including both top-2 centroids living in the SAME model shard and
     exact-tie centroids duplicated ACROSS shard boundaries;
  3. engine e2e — a full `run_loop` XL fit is bit-identical to the
     LocalEngine on a (1 data, 1 model) mesh and to the MeshEngine on
     (2 data, 1 model); on (2, 2) with N % n_shards != 0 it converges
     with every real row labeled and n_active == N_real;
  4. checkpoint/elastic-restart — XL->XL resume is bit-identical;
     XL->local and local->XL restores converge to the same quality;
  5. config rho reaches the controller (growth under rho=0.5) and the
     gb (bounds="none") family runs sharded.
"""
from repro.util.env import force_host_device_count
force_host_device_count(8)

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import jax.ops
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import api
from repro.core.distributed import (assign_top2_sharded, make_dp_round,
                                    make_xl_round)
from repro.core.state import full_mse
from repro.kernels import ops, ref

mesh = jax.make_mesh((4, 2), ("data", "model"))

rng = np.random.default_rng(0)
k, d, n = 16, 32, 8192
centers = rng.normal(size=(8, d)) * 5
X = (centers[rng.integers(0, 8, n)]
     + rng.normal(size=(n, d))).astype(np.float32)
C0 = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)

# -- 1. one-shot rounds vs an exact Lloyd oracle ---------------------------
d2o = ref.pairwise_dist2(jnp.asarray(X), C0)
ao = jnp.argmin(d2o, axis=1)
So = jax.ops.segment_sum(jnp.asarray(X), ao, num_segments=k)
vo = jax.ops.segment_sum(jnp.ones(n), ao, num_segments=k)
Co = jnp.where((vo > 0)[:, None], So / jnp.maximum(vo, 1)[:, None], C0)

Xd = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P(("data",), None)))
Cd = jax.device_put(C0, NamedSharding(mesh, P("model", None)))
Sd = jax.device_put(jnp.zeros((k, d), jnp.float32),
                    NamedSharding(mesh, P("model", None)))
vd = jax.device_put(jnp.zeros((k,), jnp.float32),
                    NamedSharding(mesh, P("model")))
round_fn = make_xl_round(mesh, k=k, data_axes=("data",),
                         model_axis="model")
C1, S1, v1, a, dd, dd2, grow, r, mse = round_fn(Xd, Cd, Sd, vd)

err_a = int(jnp.sum(a.astype(jnp.int32) != ao.astype(jnp.int32)))
err_C = float(jnp.max(jnp.abs(C1 - Co)))
# both returned distances are EUCLIDEAN now (no mixed units)
err_d = float(jnp.max(jnp.abs(dd * dd - jnp.min(d2o, axis=1))))
assert float(jnp.min(dd2 - dd)) >= 0.0, "d2 must dominate d1"
print(f"xl round: assign mismatches={err_a} "
      f"max|C-C_oracle|={err_C:.2e} mse={float(mse):.3f}")
assert err_a == 0 and err_C < 1e-3 and err_d < 1e-2

dpr = make_dp_round(mesh)
Xd8 = jax.device_put(jnp.asarray(X),
                     NamedSharding(mesh, P(("data", "model"), None)))
C1b, S1b, v1b, a_b, d_b, grow_b, r_b, mse_b = dpr(Xd8, C0)
err_a2 = int(jnp.sum(a_b.astype(jnp.int32) != ao.astype(jnp.int32)))
err_C2 = float(jnp.max(jnp.abs(C1b - Co)))
print(f"dp round: assign mismatches={err_a2} "
      f"max|C-C_oracle|={err_C2:.2e}")
assert err_a2 == 0 and err_C2 < 1e-3


# -- 2. sharded fold parity vs single-device ops.assign_top2 ---------------
def sharded_top2(x, C):
    def fn(xs, Cl):
        off = jax.lax.axis_index("model") * Cl.shape[0]
        return assign_top2_sharded(xs, Cl, model_axis="model",
                                   k_offset=off)
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(P(None, None), P("model", None)),
                       out_specs=(P(None), P(None), P(None)),
                       check_vma=False)
    return jax.jit(sm)(x, C)


xq = jnp.asarray(X[:512])
a_sh, d1_sh, d2_sh = sharded_top2(xq, C0)
a_1d, d1_1d, d2_1d = ops.assign_top2(xq, C0)
assert int(jnp.sum(a_sh != a_1d)) == 0
np.testing.assert_array_equal(np.asarray(d1_sh), np.asarray(d1_1d))
np.testing.assert_array_equal(np.asarray(d2_sh), np.asarray(d2_1d))

# same-shard top-2: centroids 2 and 3 (both in model shard 0) are the two
# nearest; cross-shard tie: C[5] == C[13] exactly (shards 0 and 1), so the
# fold must break the tie to the LOWER global index like argmin does
C_tie = np.array(C0, copy=True)
C_tie[3] = C_tie[2] + 1e-3
C_tie[13] = C_tie[5]
C_tie = jnp.asarray(C_tie)
x_tie = jnp.concatenate([C_tie[2:3] + 5e-4,      # nearest two in shard 0
                         C_tie[5:6]])            # dead tie across shards
a_t, d1_t, d2_t = sharded_top2(x_tie, C_tie)
a_r, d1_r, d2_r = ops.assign_top2(x_tie, C_tie)
np.testing.assert_array_equal(np.asarray(a_t), np.asarray(a_r))
np.testing.assert_array_equal(np.asarray(d1_t), np.asarray(d1_r))
np.testing.assert_array_equal(np.asarray(d2_t), np.asarray(d2_r))
assert int(a_t[0]) in (2, 3)             # both top-2 in model shard 0
assert int(a_t[1]) == 5                  # tie resolves to lower index
assert float(d1_t[1]) == 0.0 and float(d2_t[1]) == 0.0
print("fold parity: sharded top-2 == single-device (incl. same-shard "
      "top-2, cross-shard tie)")


# -- 3. XLEngine through run_loop ------------------------------------------
def telemetry_equal(a, b):
    """Schedule decisions (b, grow, counts, evals) must match EXACTLY;
    batch_mse is a pure-telemetry f32 sum whose in-graph reduction
    order differs between shard_map and plain-jit programs — the
    per-point distances are bit-identical (asserted via the state
    below), so it is compared to 2 ulp instead."""
    assert len(a) == len(b), (len(a), len(b))
    for ra, rb in zip(a, b):
        da, db = ra.to_dict(), rb.to_dict()
        da.pop("t"), db.pop("t")
        ma, mb = da.pop("batch_mse"), db.pop("batch_mse")
        assert da == db, (da, db)
        if ma is not None or mb is not None:
            assert abs(ma - mb) <= 4e-7 * abs(mb), (ra.round, ma, mb)


ke, de, ne = 8, 16, 4001                 # 4001: indivisible by 2 and 4
centers_e = rng.normal(size=(ke, de)) * 5
Xe = (centers_e[rng.integers(0, ke, ne)]
      + rng.normal(size=(ne, de))).astype(np.float32)
cfg = api.FitConfig(k=ke, algorithm="tb", b0=512, max_rounds=80, seed=1,
                    backend="xl", data_axes=("data",), model_axis="model",
                    capacity_floor=256)

mesh11 = jax.make_mesh((1, 1), ("data", "model"))
out_xl11 = api.fit(Xe, cfg, mesh=mesh11)
out_loc = api.fit(Xe, dataclasses.replace(cfg, backend="local"))
assert out_xl11.converged
np.testing.assert_array_equal(out_xl11.C, out_loc.C)
np.testing.assert_array_equal(out_xl11.labels, out_loc.labels)
np.testing.assert_array_equal(np.asarray(out_xl11.state.points.d),
                              np.asarray(out_loc.state.points.d))
np.testing.assert_array_equal(np.asarray(out_xl11.state.points.lb),
                              np.asarray(out_loc.state.points.lb))
telemetry_equal(out_xl11.telemetry, out_loc.telemetry)
print(f"engine e2e: XL(1,1) == LocalEngine bit-identically over "
      f"{len(out_loc.telemetry)} rounds (schedule + centroids)")

mesh21 = jax.make_mesh((2, 1), ("data", "model"))
out_xl21 = api.fit(Xe, cfg, mesh=mesh21)
out_mesh = api.fit(Xe, dataclasses.replace(cfg, backend="mesh"),
                   mesh=mesh21)
np.testing.assert_array_equal(out_xl21.C, out_mesh.C)
np.testing.assert_array_equal(out_xl21.labels, out_mesh.labels)
telemetry_equal(out_xl21.telemetry, out_mesh.telemetry)
print("engine e2e: XL(2,1) == MeshEngine(2) bit-identically")

mesh22 = jax.make_mesh((2, 2), ("data", "model"))
out22 = api.fit(Xe, cfg, mesh=mesh22)
assert out22.converged
assert int((out22.labels < 0).sum()) == 0, "real rows left unlabeled"
assert out22.telemetry[-1].b == ne      # final record capped at N_real
assert any(r.b == ne for r in out22.telemetry if r.batch_mse is not None)
mse22 = float(full_mse(jnp.asarray(Xe), jnp.asarray(out22.C)))
mse_ref = float(full_mse(jnp.asarray(Xe), jnp.asarray(out_loc.C)))
assert abs(mse22 - mse_ref) / mse_ref < 0.05, (mse22, mse_ref)
print(f"engine e2e: XL(2,2) on N={ne} converged, all rows labeled, "
      f"n_active == N_real, mse {mse22:.5f} (local {mse_ref:.5f})")

# -- 4. checkpoint / elastic restart ---------------------------------------
with tempfile.TemporaryDirectory() as ckdir:
    ck = api.CheckpointConfig(checkpoint_dir=ckdir, save_every=4)
    api.fit(Xe, dataclasses.replace(cfg, max_rounds=9, checkpoint=ck),
            mesh=mesh22)
    km = api.NestedKMeans(dataclasses.replace(cfg, checkpoint=ck),
                          mesh=mesh22)
    km.fit(Xe, resume=True)
    np.testing.assert_array_equal(out22.C, km.cluster_centers_)
    telemetry_equal(out22.telemetry, km.telemetry_)
    print("checkpoint: XL->XL resume bit-identical")

with tempfile.TemporaryDirectory() as ckdir:
    ck = api.CheckpointConfig(checkpoint_dir=ckdir, save_every=4)
    api.fit(Xe, dataclasses.replace(cfg, max_rounds=9, checkpoint=ck),
            mesh=mesh22)
    kml = api.NestedKMeans(dataclasses.replace(cfg, backend="local",
                                               checkpoint=ck))
    kml.fit(Xe, resume=True)
    assert kml.converged_
    msel = float(full_mse(jnp.asarray(Xe),
                          jnp.asarray(kml.cluster_centers_)))
    assert abs(msel - mse_ref) / mse_ref < 0.05, (msel, mse_ref)

with tempfile.TemporaryDirectory() as ckdir:
    ck = api.CheckpointConfig(checkpoint_dir=ckdir, save_every=4)
    api.fit(Xe, dataclasses.replace(cfg, backend="local", max_rounds=9,
                                    checkpoint=ck))
    kmx = api.NestedKMeans(dataclasses.replace(cfg, checkpoint=ck),
                           mesh=mesh22)
    kmx.fit(Xe, resume=True)
    assert kmx.converged_
    msex = float(full_mse(jnp.asarray(Xe),
                          jnp.asarray(kmx.cluster_centers_)))
    assert abs(msex - mse_ref) / mse_ref < 0.05, (msex, mse_ref)
print("checkpoint: XL<->local elastic restores converge to the same "
      "quality")

# -- 5. rho threading + the gb family sharded ------------------------------
out_rho = api.fit(Xe, dataclasses.replace(cfg, rho=0.5, max_rounds=12),
                  mesh=mesh22)
assert any(r.grow for r in out_rho.telemetry), \
    "rho=0.5 never reached the sharded controller"
out_gb = api.fit(Xe, dataclasses.replace(cfg, algorithm="gb"),
                 mesh=mesh22)
assert out_gb.converged and int((out_gb.labels < 0).sum()) == 0
print("rho threading + gb-on-xl OK")

print("xl smoke OK")
