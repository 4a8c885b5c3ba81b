"""Out-of-core benchmark: cluster a dataset bigger than you'd want in RAM.

The claim (the `repro.data.store` data plane): a multi-process fit
streamed off a chunked on-disk store

  * is BIT-IDENTICAL (round-by-round telemetry) to the same fit with the
    data in host memory — out-of-core is a placement strategy, not an
    approximation;
  * keeps each process's fit-attributable peak RSS under a budget BELOW
    the dataset's own ``n*d*4`` bytes (the in-memory fit needs ~2.5x the
    dataset: the rows, their permuted copy, and the device buffer), and
    measurably under the in-memory fit's footprint;
  * reads at most ~1.1x one full-data pass off disk per fit — the
    blocked permutation keeps the nested schedule's disk frontier
    chunk-sequential, so each chunk is loaded about once (a uniform
    shuffle would cost ~log2(n/b0) passes);
  * still beats the dense one-shot schedule on recompute work to reach
    a COMMON quality target — 1.01x the best validation MSE that both
    schedules attain (the paper's work claim, unchanged by the data
    living on disk). Both baselines start from the identical C0 (the
    dense fit consumes the same permuted row sequence), but k-means
    minima are init-sensitive enough that either schedule can converge
    a few percent past the other at any given n; targeting the quality
    BOTH provably reach keeps the gate about WORK, never about which
    basin a run happened to land in (time-to-quality, MLPerf-style).

The fits need forced host devices and real process boundaries (RSS is a
per-process number), so every measurement runs in CHILD processes: four
`jax.distributed` processes for the streamed and in-memory fits, one
local process for the dense baseline. Four processes because the RSS
gate needs them: a process's floor is ~2.3x ITS data share (device
buffer + the first full-batch round's recompute gather + the distance
matrix) plus a ~240 MB jax runtime — only at P >= 4 does that land
well under the dataset's own bytes. The parent writes the store,
orchestrates, and gates on the children's JSON reports.

Artifact: artifacts/bench/outofcore.json
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

ART = Path(__file__).resolve().parent.parent / "artifacts" / "bench"
REPO = Path(__file__).resolve().parent.parent

N_PROC = 4
DEV_PER_PROC = 1    # 4 shards total, same layout as 2x2
DIM = 64
K = 16
CLASSES = 16
# moderately overlapping blobs. The spread is a protocol knob with
# failure modes on BOTH sides, all measured at this n and d: well
# separated (spread >= 1.5: centers ~17 apart vs noise radius 8 in 64
# dims) the k == classes problem has a snap-to-blobs global minimum
# that only the dense baseline reliably finds, and the work gate
# becomes a local-minima lottery; heavily overlapping (spread <= 0.5)
# the density is so smooth that centroids drift on near-flat valleys,
# the growth controller never sees movement settle, and b crawls — the
# fit never streams the store. At spread 1.0 the minima are
# near-equivalent (final val MSEs within ~1%, either schedule can win)
# and b doubles steadily to n, so the gates measure what they claim:
# recompute WORK to the same quality, over a fit that actually runs
# the full out-of-core path.
SPREAD = 1.0
SEED = 0
N_VAL = 20_000
VAL_BLOCK = 1 << 20              # disjoint from the writer's block range


def _params(quick: bool):
    n = 6_000_000 if quick else 10_000_000
    chunk_rows = 16_384
    data_bytes = n * DIM * 4
    # per-process budget, from the measured footprint model: ~400-450
    # MB of jax runtime + compile caches (one executable per b/capacity
    # bucket), the device buffer (data/P), and the big-b round scratch
    # — the first round at a fresh prefix gathers ~every row once more
    # (another data/P) plus the (rows x k) distance block; measured
    # ~2.2x data/P across scales. The constants below cover that with
    # ~10% headroom and sit well below data_bytes — which is what the
    # IN-memory fit's working set (rows + permuted copy + buffer)
    # costs per process.
    budget = int(560e6 + 2.35 * data_bytes / N_PROC)
    return n, chunk_rows, data_bytes, budget


def _cost_to_target(telemetry, target):
    """(recompute_work, rounds) until val_mse first reaches ``target``
    over dict telemetry records; (None, None) if the run never does."""
    work = 0
    rounds = 0
    for rec in telemetry:
        if rec["batch_mse"] is not None:
            work += rec["n_recomputed"]
            rounds += 1
        if rec["val_mse"] is not None and rec["val_mse"] <= target:
            return work, rounds
    return None, None


# ---------------------------------------------------------------------------
# children (all measurement happens here)
# ---------------------------------------------------------------------------

def child(role: str, proc: int, port: str, workdir: str,
          quick: bool) -> None:
    from repro.util.env import force_host_device_count
    force_host_device_count(DEV_PER_PROC if role != "dense" else 1)
    import dataclasses
    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.data.store import ChunkStore
    from repro.data.store.writer import blob_rows
    from repro.launch.mesh import initialize_multihost

    n, chunk_rows, data_bytes, _ = _params(quick)
    if role != "dense":
        initialize_multihost(coordinator_address=f"localhost:{port}",
                             num_processes=N_PROC, process_id=proc)
    jnp.zeros((8,)).block_until_ready()          # backend is up
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

    base = api.FitConfig(
        k=K, algorithm="tb", rho=float("inf"), b0=4096,
        bounds="hamerly2", eval_every=1, max_rounds=45,
        capacity_floor=4096, seed=SEED)
    X_val = blob_rows(N_VAL, dim=DIM, classes=CLASSES, seed=SEED,
                      spread=SPREAD, block=VAL_BLOCK)
    store_dir = os.path.join(workdir, "store")

    metrics = None
    if role == "stream":
        st = ChunkStore(store_dir)
        cfg = dataclasses.replace(base, backend="multihost")
        out = api.fit(st, cfg, X_val=X_val)
        metrics = st.metrics.to_dict()
    elif role == "inmem":
        # the honest in-memory comparison point: load ALL rows, permute
        # them into the streamed fit's exact row sequence, fit with the
        # shuffle disabled — bit-identical telemetry, in-RAM footprint
        from repro.data.store import store_permutation
        st = ChunkStore(store_dir)
        X = st.rows(0, st.n)
        X = X[store_permutation(st.n, st.chunk_rows, SEED)]
        st.close()
        cfg = dataclasses.replace(base, backend="multihost",
                                  shuffle=False)
        out = api.fit(X, cfg, X_val=X_val)
    elif role == "dense":
        # same permuted sequence as the streamed fit, so the one-shot
        # baseline starts from the IDENTICAL first-k-rows C0 — the
        # work comparison is schedule vs schedule, not init vs init
        from repro.data.store import store_permutation
        st = ChunkStore(store_dir)
        X = st.rows(0, st.n)
        X = X[store_permutation(st.n, st.chunk_rows, SEED)]
        st.close()
        cfg = dataclasses.replace(base, algorithm="gb", b0=n,
                                  max_rounds=12, shuffle=False)
        out = api.fit(X, cfg, X_val=X_val)
    else:
        raise ValueError(role)

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    telem = [r.to_dict() for r in out.telemetry]
    for r in telem:
        r.pop("t")
    report = {
        "role": role, "proc": proc, "quick": quick,
        "rss0": rss0, "rss_peak": peak, "rss_delta": peak - rss0,
        "store_metrics": metrics, "telemetry": telem,
        "converged": bool(out.converged), "final_val_mse": out.final_mse,
        "config": out.config.to_dict(),
    }
    with open(os.path.join(workdir, f"{role}_{proc}.json"), "w") as f:
        json.dump(report, f)
    print(f"[outofcore child {role}/{proc}] rounds={len(telem)} "
          f"converged={out.converged} final_val={out.final_mse:.5f} "
          f"rss_delta={(peak - rss0) / 1e6:.0f}MB", flush=True)


# ---------------------------------------------------------------------------
# parent: store build, orchestration, gates
# ---------------------------------------------------------------------------

def _spawn(role, workdir, quick, n_proc):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])
    from benchmarks.common import cpu_child_env
    env = cpu_child_env()
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "benchmarks.outofcore", "--child",
           role, "%d", port, workdir] + ([] if quick else ["--full"])
    procs = [subprocess.Popen([a if a != "%d" else str(i) for a in cmd],
                              env=env, cwd=REPO)
             for i in range(n_proc)]
    for p in procs:
        if p.wait(timeout=1800) != 0:
            raise RuntimeError(f"outofcore child {role} failed")
    reports = []
    for i in range(n_proc):
        with open(os.path.join(workdir, f"{role}_{i}.json")) as f:
            reports.append(json.load(f))
    return reports


def main(quick: bool = True) -> bool:
    from benchmarks import common

    n, chunk_rows, data_bytes, budget = _params(quick)
    workdir = tempfile.mkdtemp(prefix="outofcore_bench_")
    store_dir = os.path.join(workdir, "store")
    print(f"  writing {n:,} x {DIM} f32 rows ({data_bytes / 1e9:.2f} GB) "
          f"to {store_dir} ...", flush=True)
    from repro.data.store.writer import write_synthetic_store
    write_synthetic_store(store_dir, n=n, dim=DIM, classes=CLASSES,
                          seed=SEED, spread=SPREAD, chunk_rows=chunk_rows)

    try:
        stream = _spawn("stream", workdir, quick, N_PROC)
        inmem = _spawn("inmem", workdir, quick, N_PROC)
        dense = _spawn("dense", workdir, quick, 1)[0]
        print(f"  ({common.CPU_REHEARSAL['note']})")
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    common.record_manifest("outofcore", stream[0]["config"])
    common.record_manifest("outofcore", dense["config"])

    dense_min = min(r["val_mse"] for r in dense["telemetry"]
                    if r["val_mse"] is not None)
    stream_min = min(r["val_mse"] for r in stream[0]["telemetry"]
                     if r["val_mse"] is not None)
    target = 1.01 * max(dense_min, stream_min)
    s_work, s_rounds = _cost_to_target(stream[0]["telemetry"], target)
    d_work, d_rounds = _cost_to_target(dense["telemetry"], target)
    s_rss = max(r["rss_delta"] for r in stream)
    m_rss = min(r["rss_delta"] for r in inmem)
    reads = max(r["store_metrics"]["bytes_read"] for r in stream)

    ok = True
    ok &= common.check(
        "outofcore-bit-parity",
        stream[0]["telemetry"] == inmem[0]["telemetry"]
        and stream[0]["telemetry"] == stream[1]["telemetry"],
        f"streamed == in-memory telemetry over "
        f"{len(stream[0]['telemetry'])} rounds, on both processes")
    ok &= common.check(
        "outofcore-rss-budget",
        s_rss <= budget < data_bytes,
        f"streamed peak ΔRSS {s_rss / 1e6:.0f}MB <= budget "
        f"{budget / 1e6:.0f}MB < data {data_bytes / 1e6:.0f}MB")
    ok &= common.check(
        "outofcore-rss-vs-inmem", s_rss < m_rss,
        f"streamed {s_rss / 1e6:.0f}MB < in-memory {m_rss / 1e6:.0f}MB "
        f"per process")
    ok &= common.check(
        "outofcore-read-amplification", reads <= 1.1 * data_bytes,
        f"worst process read {reads / 1e6:.0f}MB = "
        f"{reads / data_bytes:.2f}x one full pass")
    reached = s_work is not None and d_work is not None
    ok &= common.check(
        "outofcore-reach-common-quality", reached,
        f"rounds to 1.01x the common attained val: streamed={s_rounds} "
        f"dense={d_rounds}")
    ok &= common.check(
        "outofcore-nested-beats-dense",
        reached and s_work < d_work,
        "" if not reached else
        f"to common quality: streamed nested {s_work:,} k-scans "
        f"({s_work / n:.2f} full-data passes) vs dense {d_work:,} "
        f"({d_work / n:.2f})")

    report = {
        "device": common.CPU_REHEARSAL,
        "quick": quick, "n": n, "d": DIM, "k": K,
        "chunk_rows": chunk_rows, "data_bytes": data_bytes,
        "rss_budget": budget, "dense_min": dense_min,
        "stream_min": stream_min,
        "stream": {"rss_delta": [r["rss_delta"] for r in stream],
                   "bytes_read": [r["store_metrics"]["bytes_read"]
                                  for r in stream],
                   "store_metrics": stream[0]["store_metrics"],
                   "work_to_1pct": s_work, "rounds_to_1pct": s_rounds,
                   "n_rounds": len(stream[0]["telemetry"]),
                   "converged": stream[0]["converged"],
                   "final_val_mse": stream[0]["final_val_mse"],
                   "config": stream[0]["config"]},
        "inmem": {"rss_delta": [r["rss_delta"] for r in inmem]},
        "dense": {"rss_delta": dense["rss_delta"],
                  "work_to_1pct": d_work, "rounds_to_1pct": d_rounds,
                  "n_rounds": len(dense["telemetry"]),
                  "converged": dense["converged"],
                  "final_val_mse": dense["final_val_mse"],
                  "config": dense["config"]},
        "checks_pass": bool(ok),
    }
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "outofcore.json").write_text(json.dumps(report, indent=1))
    print(f"  wrote {ART / 'outofcore.json'}")
    return ok


if __name__ == "__main__":
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        child(sys.argv[i + 1], int(sys.argv[i + 2]), sys.argv[i + 3],
              sys.argv[i + 4], quick="--full" not in sys.argv)
    else:
        sys.exit(0 if main(quick="--full" not in sys.argv) else 1)
