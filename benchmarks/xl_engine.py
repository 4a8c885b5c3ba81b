"""XL-engine benchmark: the nested schedule vs the dense one-shot round.

The claim (paper Alg. 6/9, transplanted to the centroid-sharded
engine): driving the XL round with the nested grow-batch schedule
reaches within 1% of the empirical-minimum validation MSE with FAR less
work than the dense one-shot round (full batch, fresh stats every
round — what `make_xl_round` did before the engine existed). Work is
counted in points touched; "equivalent rounds" normalises it by N so
the two schedules compare in units of full-data passes.

The fits need a multi-device host mesh, so the measurement runs in a
CHILD process (`python -m benchmarks.xl_engine --child`) with forced
host devices; the parent validates the claim from the artifact and
records the child's resolved FitConfig manifests.

Artifact: artifacts/bench/xl_engine.json
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

ART = Path(__file__).resolve().parent.parent / "artifacts" / "bench"
REPO = Path(__file__).resolve().parent.parent


# --------------------------------------------------------------------------
# child: the actual fits (forced host devices)
# --------------------------------------------------------------------------

def _cost_to_target(telemetry, target):
    """(compute_seconds, recompute_work, rounds) until val_mse first
    reaches ``target``; (None,)*3 if the run never does.

    ``recompute_work`` counts the points whose distances were actually
    recomputed (full k-scans) — the honest per-round cost of a bounded
    nested round, where n_active includes settled points the bound test
    skipped. For the dense one-shot round the two coincide at N.
    """
    work = 0
    rounds = 0
    for rec in telemetry:
        if rec.batch_mse is not None:       # compute rounds only
            work += rec.n_recomputed
            rounds += 1
        if rec.val_mse is not None and rec.val_mse <= target:
            return rec.t, work, rounds
    return None, None, None


def child(quick: bool) -> None:
    from repro.util.env import force_host_device_count
    force_host_device_count(8)
    import dataclasses

    import jax

    from repro import api
    from repro.data.synthetic import infmnist_like

    # infMNIST-like stand-in (same family as fig1), over-segmented:
    # k >> the 10 underlying classes, so every schedule faces the same
    # landscape of near-equivalent minima — the paper's Fig. 1 protocol.
    n, k = (12_000, 32) if quick else (40_000, 64)
    mesh_shape = (2, 2) if quick else (4, 2)
    X = infmnist_like(n + n // 10, seed=0)
    X, X_val = X[:n], X[n:]
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))

    base = api.FitConfig(
        k=k, algorithm="tb", rho=float("inf"), b0=256,
        bounds="hamerly2", backend="xl", data_axes=("data",),
        model_axis="model", eval_every=1,
        max_rounds=120 if quick else 200,
        capacity_floor=256, seed=0)
    dense = dataclasses.replace(base, algorithm="gb", b0=n)

    runs = {}
    for name, cfg in (("nested", base), ("dense", dense)):
        out = api.fit(X, cfg, X_val=X_val, mesh=mesh)
        runs[name] = out
        print(f"[xl child] {name}: rounds={len(out.telemetry)} "
              f"converged={out.converged} final_val={out.final_mse:.5f}",
              flush=True)

    emp_min = min(rec.val_mse
                  for out in runs.values()
                  for rec in out.telemetry if rec.val_mse is not None)
    target = 1.01 * emp_min
    from benchmarks.common import CPU_REHEARSAL
    report = {"device": CPU_REHEARSAL,
              "quick": quick, "n": n, "d": X.shape[1], "k": k,
              "mesh": list(mesh_shape), "empirical_min": emp_min}
    for name, out in runs.items():
        t, work, rounds = _cost_to_target(out.telemetry, target)
        report[name] = {
            "t_to_1pct_s": t, "work_to_1pct": work,
            "rounds_to_1pct": rounds,
            "equiv_rounds_to_1pct": (None if work is None else work / n),
            "n_rounds": len(out.telemetry),
            "converged": bool(out.converged),
            "final_val_mse": out.final_mse,
            "config": out.config.to_dict(),
        }
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "xl_engine.json").write_text(json.dumps(report, indent=1))
    print(f"[xl child] wrote {ART / 'xl_engine.json'}", flush=True)


# --------------------------------------------------------------------------
# parent: suite entry point
# --------------------------------------------------------------------------

def main(quick: bool = True) -> bool:
    from benchmarks import common

    env = common.cpu_child_env()
    cmd = [sys.executable, "-m", "benchmarks.xl_engine", "--child"]
    if not quick:
        cmd.append("--full")
    try:
        r = subprocess.run(cmd, env=env, cwd=REPO, text=True,
                           capture_output=True, timeout=1800)
    except subprocess.TimeoutExpired as e:
        # funnel through the claim-check machinery like every other
        # failure so the runner still prints its summary
        sys.stdout.write((e.stdout or b"").decode(errors="replace")
                         if isinstance(e.stdout, bytes)
                         else (e.stdout or ""))
        return common.check("xl-child", False,
                            "child timed out after 1800s")
    sys.stdout.write(r.stdout)
    print(f"  ({common.CPU_REHEARSAL['note']})")
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        return common.check("xl-child", False, "child process failed")

    rep = json.loads((ART / "xl_engine.json").read_text())
    for name in ("nested", "dense"):
        common.record_manifest("xl", rep[name]["config"])

    nested, dense = rep["nested"], rep["dense"]
    ok = True
    reached = (nested["work_to_1pct"] is not None
               and dense["work_to_1pct"] is not None)
    ok &= common.check(
        "xl-both-reach-1pct", reached,
        f"nested={nested['rounds_to_1pct']} dense="
        f"{dense['rounds_to_1pct']} rounds")
    # gate on recompute work (full k-distance scans) — the hardware-
    # independent cost the paper's speedup derives from. Wall time is
    # reported for context but not gated: at this CI toy scale the
    # forced-host-device dispatch overhead of ~40 cheap nested rounds
    # swamps the compute it saves, which is the opposite of the
    # production regime (where one full k=10^5 pass dwarfs dispatch).
    ok &= common.check(
        "xl-nested-beats-dense",
        reached and nested["work_to_1pct"] < dense["work_to_1pct"],
        "" if not reached else
        f"to-1%-of-min: nested {nested['work_to_1pct']:,} k-scans "
        f"({nested['equiv_rounds_to_1pct']:.2f} full-data passes, "
        f"{nested['t_to_1pct_s']:.2f}s) vs "
        f"dense {dense['work_to_1pct']:,} "
        f"({dense['equiv_rounds_to_1pct']:.2f}, "
        f"{dense['t_to_1pct_s']:.2f}s)")
    ok &= common.check(
        "xl-nested-converges", nested["converged"],
        f"final val {nested['final_val_mse']:.5f} "
        f"(empirical min {rep['empirical_min']:.5f})")
    return ok


if __name__ == "__main__":
    if "--child" in sys.argv:
        child(quick="--full" not in sys.argv)
    else:
        sys.exit(0 if main(quick="--full" not in sys.argv) else 1)
