import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any other import: jax locks the device count on first
#   init. 512 placeholder host devices stand in for the production pods.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, in artifacts/dryrun/<cell>.json:
  * memory_analysis()  — per-device bytes (proves the cell fits HBM)
  * cost_analysis()    — per-device HLO FLOPs / bytes
  * collective wire bytes parsed from the partitioned HLO
  * the three-term roofline (repro.roofline.analysis)

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--kmeans]
  python -m repro.launch.dryrun --arch ... --shape ... --dump-hlo f.txt
"""
import argparse
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import input_specs as ispec
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as shd
from repro.optim import adamw
from repro.roofline import analysis as ra
from repro.roofline import hlo_cost
from repro.train import step as tstep

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"
#: the dry-run's production mesh is a pod of v5e chips
_PEAKS = ra.PEAKS[ra.V5E]


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def n_micro_for(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    """One sequence per data shard per microbatch."""
    dp = shd.axis_size(mesh, shd.data_axes(mesh))
    return max(1, shape.global_batch // dp)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Build (jitted fn, abstract args, in_shardings) for one cell."""
    params_s = ispec.abstract_params(cfg)
    pshard = shd.param_shardings(cfg, mesh, params_s)

    if shape.kind == "train":
        batch_s = ispec.train_batch_specs(cfg, shape)
        bshard = shd.tree_shardings(
            mesh, shd.batch_specs(cfg, mesh, batch_s))
        opt_s = ispec.abstract_opt_state(params_s)
        oshard = adamw.AdamWState(
            mu=shd.param_shardings(cfg, mesh, opt_s.mu),
            nu=shd.param_shardings(cfg, mesh, opt_s.nu),
            count=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        fn = tstep.make_train_step(
            cfg, n_micro=n_micro_for(cfg, shape, mesh),
            accum_dtype=(jnp.bfloat16 if cfg.param_count() > 1e11
                         else jnp.float32))
        args = (params_s, opt_s, batch_s)
        in_sh = (pshard, oshard, bshard)
        tokens = shape.global_batch * shape.seq_len
        model_flops = ra.model_flops_train(cfg.active_param_count(), tokens)
    elif shape.kind == "prefill":
        batch_s = ispec.prefill_batch_specs(cfg, shape)
        bshard = shd.tree_shardings(
            mesh, shd.batch_specs(cfg, mesh, batch_s))
        fn = tstep.make_prefill_step(cfg, cache_len=shape.seq_len)
        args = (params_s, batch_s)
        in_sh = (pshard, bshard)
        tokens = shape.global_batch * shape.seq_len
        model_flops = ra.model_flops_fwd(cfg.active_param_count(), tokens)
    else:  # decode
        dec = ispec.decode_specs(cfg, shape)
        cshard = shd.tree_shardings(
            mesh, shd.cache_specs(cfg, mesh, dec["cache"]))
        dp = shd.data_axes(mesh)
        tok_ax = dp if shape.global_batch % shd.axis_size(mesh, dp) == 0 \
            else None
        tshard = jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec(tok_ax, None))
        fn = tstep.make_decode_step(cfg)
        args = (params_s, dec["token"], dec["cache"])
        in_sh = (pshard, tshard, cshard)
        tokens = shape.global_batch            # one token per sequence
        model_flops = ra.model_flops_fwd(cfg.active_param_count(), tokens)

    return fn, args, in_sh, model_flops


def run_cell(arch: str, shape: ShapeConfig, *, multi_pod: bool,
             out_dir: Path = ARTIFACTS, dump_hlo: str | None = None,
             tag: str = "") -> dict:
    cfg = configs.get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = f"{arch}__{shape.name}__{_mesh_tag(multi_pod)}{tag}"
    t0 = time.time()
    rec: dict = {"cell": cell, "arch": arch, "shape": shape.name,
                 "mesh": list(mesh.shape.values()),
                 "axes": list(mesh.axis_names), "kind": shape.kind}
    try:
        fn, args, in_sh, model_flops = lower_cell(cfg, shape, mesh)
        # donate params/opt (train) or cache (decode): the updated state
        # aliases the input buffers, as the real launcher runs it
        donate = (0, 1) if shape.kind == "train" else \
            (2,) if shape.kind == "decode" else ()
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        # analytic per-device storage floor from the input shardings
        # (CPU BufferAssignment ignores donation and keeps separate
        # input+output copies, so memory_analysis() overstates steady
        # state for donated train/decode steps — both views recorded).
        def _dev_bytes(leaf, sh):
            n = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            if hasattr(sh, "spec"):
                for dim, ax in enumerate(sh.spec):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    size = int(np.prod([mesh.shape[a] for a in axes]))
                    if leaf.shape[dim] % size == 0:
                        n //= size
            return n
        storage = sum(
            _dev_bytes(l, s) for l, s in zip(
                jax.tree.leaves(args), jax.tree.leaves(
                    in_sh, is_leaf=lambda x: hasattr(x, "spec"))))
        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes": int(mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes),
                "storage_bytes_analytic": storage,
                "source": "memory_analysis",
            }
        except Exception as e:  # CPU backend may not implement it
            rec["memory"] = {"storage_bytes_analytic": storage,
                             "peak_bytes": None,
                             "source": f"analytic({type(e).__name__})"}

        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        if dump_hlo:
            Path(dump_hlo).write_text(hlo)
        # loop-aware per-device costs (XLA's cost_analysis counts while
        # bodies once; hlo_cost multiplies through trip counts)
        hc = hlo_cost.analyze(hlo)
        coll = ra.parse_collectives(hlo)   # static (per-occurrence) view
        flops, hbm = hc.flops, hc.bytes
        n_chips = len(jax.devices())
        roof = ra.roofline_terms(flops, hbm, hc.wire, peaks=_PEAKS,
                                 model_flops=model_flops / n_chips)
        rec.update({
            "ok": True,
            "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "flops_per_device": flops,
            "hbm_bytes_per_device": hbm,
            "wire_bytes_per_device": hc.wire,
            "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                                  "bytes": float(cost.get("bytes accessed",
                                                          0.0))},
            "collectives": hc.wire_by_kind,
            "collective_counts": coll.counts,
            "model_flops_per_device": model_flops / n_chips,
            "roofline": {
                "compute_s": roof.compute_s,
                "memory_s": roof.memory_s,
                "collective_s": roof.collective_s,
                "bottleneck": roof.bottleneck,
                "useful_ratio": roof.useful_ratio,
                "roofline_fraction": roof.roofline_fraction(),
            },
        })
    except Exception as e:
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell}.json").write_text(json.dumps(rec, indent=1))
    status = "OK " if rec.get("ok") else "FAIL"
    roofstr = ""
    if rec.get("ok"):
        r = rec["roofline"]
        m = rec.get("memory", {})
        peak = m.get("peak_bytes")
        roofstr = (f" comp={r['compute_s']:.3g}s mem={r['memory_s']:.3g}s"
                   f" coll={r['collective_s']:.3g}s -> {r['bottleneck']}"
                   + (f" | peak/dev={peak / 1e9:.2f}GB" if peak else "")
                   + f" flops/dev={rec['flops_per_device']:.3g}")
    print(f"[{status}] {cell}{roofstr}", flush=True)
    return rec


def run_kmeans_cell(name: str, *, multi_pod: bool,
                    out_dir: Path = ARTIFACTS) -> dict:
    """Dry-run of the paper's own technique at production scale."""
    from repro.core import rounds as kr
    from repro.core import distributed as kd
    from repro.core.state import KMeansState, ClusterStats, PointState

    kcfg = configs.get_kmeans_config(name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = f"{name}__round__{_mesh_tag(multi_pod)}"
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_dp = shd.axis_size(mesh, dp_axes)
    t0 = time.time()
    rec: dict = {"cell": cell, "arch": name, "shape": "round",
                 "mesh": list(mesh.shape.values()),
                 "axes": list(mesh.axis_names), "kind": "kmeans"}
    try:
        N, d, k = kcfg.n_points, kcfg.dim, kcfg.k
        N += -N % n_dp                   # structural tail padding
        n_local = N // n_dp
        b_local = max(1, min(kcfg.b0 * 64, N) // n_dp)
        if kcfg.shard_centroids:
            # optimized production round: pure DP over every axis,
            # C replicated (see distributed.make_dp_round docstring).
            n_chips_all = len(jax.devices())
            N += -N % n_chips_all
            fn = kd.make_dp_round(mesh, rho=kcfg.rho)
            args = (jax.ShapeDtypeStruct((N, d), jnp.float32),
                    jax.ShapeDtypeStruct((k, d), jnp.float32))
            lowered = fn.lower(*args)
            # single-X-pass Pallas traffic model (the TPU execution path;
            # interpret-mode lowering can't appear in CPU HLO):
            n_loc = N // n_chips_all
            rec["pallas_analytic"] = {
                "hbm_bytes": n_loc * d * 4 + k * d * 4 * 3 + n_loc * 12,
                # scores dot (2ndk) + one-hot S accumulation dot (2ndk):
                # the dense round's honest MXU cost is 4ndk. In nested
                # steady state the S term shrinks to changed points only
                # (delta updates) and bounds prune the scores dot.
                "flops": 4.0 * n_loc * d * k + 4.0 * n_loc * k,
                "note": "fused_round kernel: X once + C + outputs",
            }
        else:
            fn = kd.make_sharded_round(
                mesh, dp_axes, b_local=b_local, rho=kcfg.rho,
                bounds=kcfg.bounds, capacity=max(256, b_local // 4))
            state = jax.eval_shape(functools.partial(
                _abstract_kmeans_state, n=N, d=d, k=k))
            args = (jax.ShapeDtypeStruct((N, d), jnp.float32), state)
            lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        hc = hlo_cost.analyze(compiled.as_text())
        flops, hbm = hc.flops, hc.bytes
        # useful work: one fused assign round = 2 b d k / chips flops
        n_chips = len(jax.devices())
        b_glob = N if kcfg.shard_centroids else b_local * n_dp
        model_flops = 2.0 * b_glob * d * k / n_chips
        roof = ra.roofline_terms(flops, hbm, hc.wire, peaks=_PEAKS,
                                 model_flops=model_flops)
        if "pallas_analytic" in rec:
            pa = rec["pallas_analytic"]
            pr = ra.roofline_terms(pa["flops"], pa["hbm_bytes"], hc.wire,
                                   peaks=_PEAKS, model_flops=model_flops)
            pa["roofline"] = {
                "compute_s": pr.compute_s, "memory_s": pr.memory_s,
                "collective_s": pr.collective_s,
                "bottleneck": pr.bottleneck,
                "roofline_fraction": pr.roofline_fraction(),
            }
        try:
            mem = compiled.memory_analysis()
            peak = int(mem.argument_size_in_bytes
                       + mem.temp_size_in_bytes)
        except Exception:
            peak = None
        rec.update({
            "ok": True, "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "flops_per_device": flops, "hbm_bytes_per_device": hbm,
            "wire_bytes_per_device": hc.wire,
            "collectives": hc.wire_by_kind,
            "model_flops_per_device": model_flops,
            "memory": {"peak_bytes": peak},
            "roofline": {
                "compute_s": roof.compute_s, "memory_s": roof.memory_s,
                "collective_s": roof.collective_s,
                "bottleneck": roof.bottleneck,
                "useful_ratio": roof.useful_ratio,
                "roofline_fraction": roof.roofline_fraction(),
            },
        })
    except Exception as e:
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell}.json").write_text(json.dumps(rec, indent=1))
    print(f"[{'OK ' if rec.get('ok') else 'FAIL'}] {cell}", flush=True)
    return rec


def _abstract_kmeans_state(n: int, d: int, k: int):
    from repro.core.state import ClusterStats, KMeansState, PointState
    return KMeansState(
        stats=ClusterStats(C=jnp.zeros((k, d), jnp.float32),
                           S=jnp.zeros((k, d), jnp.float32),
                           v=jnp.zeros((k,), jnp.float32),
                           sse=jnp.zeros((k,), jnp.float32),
                           p=jnp.zeros((k,), jnp.float32)),
        points=PointState(a=jnp.zeros((n,), jnp.int32),
                          d=jnp.zeros((n,), jnp.float32),
                          lb=jnp.zeros((n,), jnp.float32)),
        elkan=None, round=jnp.zeros((), jnp.int32))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kmeans", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose artifact JSON already has ok=true")
    args = ap.parse_args()
    out = Path(args.out)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    def done(cell: str) -> bool:
        f = out / f"{cell}.json"
        if not (args.skip_existing and f.exists()):
            return False
        try:
            return json.loads(f.read_text()).get("ok", False)
        except Exception:
            return False

    n_fail = 0
    if args.kmeans:
        for name in configs.KMEANS_WORKLOADS:
            for mp in meshes:
                if done(f"{name}__round__{_mesh_tag(mp)}"):
                    continue
                rec = run_kmeans_cell(name, multi_pod=mp, out_dir=out)
                n_fail += 0 if rec.get("ok") else 1
    if args.all:
        for arch in configs.list_archs():
            cfg = configs.get_config(arch)
            for shape in configs.shapes_for(cfg):
                for mp in meshes:
                    if done(f"{arch}__{shape.name}__{_mesh_tag(mp)}"):
                        continue
                    rec = run_cell(arch, shape, multi_pod=mp, out_dir=out)
                    n_fail += 0 if rec.get("ok") else 1
    elif args.arch:
        shape = {s.name: s for s in configs.ALL_SHAPES}[args.shape]
        for mp in meshes:
            rec = run_cell(args.arch, shape, multi_pod=mp, out_dir=out,
                           dump_hlo=args.dump_hlo)
            n_fail += 0 if rec.get("ok") else 1
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
