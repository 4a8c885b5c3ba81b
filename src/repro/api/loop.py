"""THE host control loop, shared by every engine — and every process.

One `run_loop` drives the growth schedule, power-of-two capacity
bucketing, overflow retry, convergence patience, telemetry and in-loop
checkpointing for all backends (local / mesh / xl / multihost).

## The process-replicated control-flow invariant

On a multi-process (jax.distributed) run EVERY process executes this
loop over its own copy of the host state. There is no leader election
and no per-round consensus protocol; instead the loop is written so its
control flow is bit-identical on every process BY CONSTRUCTION:

  * every per-round decision — batch growth (`info.grow`), capacity
    sizing (`info.n_recomputed`), overflow retry (`info.overflow`),
    convergence patience (`info.n_changed` / `info.p_max` /
    `info.n_active`) — branches ONLY on scalars out of `RoundInfo`,
    which the round functions psum-reduce across every data shard
    before returning. A replicated device scalar fetched on two
    processes yields the same bits, so both take the same branch.
  * the data placement, initial centroids and the mini-batch resampling
    stream are all seeded deterministically from the resolved
    `FitConfig` (`config.seed`), never from ambient host entropy, so
    every process holds the same global shuffle and the same schedule
    inputs at round 0.
  * the ONE intrinsically host-local quantity — the wall clock behind
    `time_budget_s` — is resolved by the coordinator and broadcast
    through `run.sync_flag` before anyone acts on it (clocks drift;
    replicated flags do not). With the default infinite budget the
    hook is never consulted.
  * filesystem facts (which checkpoint step is latest, its metadata)
    go through `run.resolve_resume`, which multi-process runs answer
    on the coordinator and broadcast.

Checkpoint writes are coordinator-only (`run.is_coordinator`), with a
`run.barrier()` after every save/clear so no process races ahead of a
directory state it may later restore from. `run.capture` / `restore`
are collectives — every process participates in the gathers and
broadcasts even though only one touches the disk.

The RoundInfo scalars land on the host at ONE site — `fetch_round_info`,
called once per round (once per overflow attempt) — and every branch
below it reads the resulting plain-Python `HostRoundInfo`. That makes
the invariant mechanically checkable: `repro.analysis` lints this module
for branches that do not derive from `HostRoundInfo` / the resolved
config / the sanctioned `run` primitives (`python -m repro.analysis
lint`), and audits a live fit for device->host syncs outside the
`LoopAudit` sanctioned scopes (`python -m repro.analysis hostsync`).
Both run in CI via scripts/ci_static.sh.

Anything appended to this loop must preserve the invariant: derive new
decisions from `RoundInfo` (extend it if needed — it is psum-reduced in
one place per engine, and lands via `fetch_round_info`), or route them
through a `run` hook that guarantees replication — and keep the
checkers green rather than allowlisting around them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.api.config import FitConfig
from repro.api.engines.base import EngineRun, ObsSink, as_sink
from repro.api.telemetry import RoundCallback, Telemetry, final_val_mse
from repro.checkpoint.store import CheckpointStore
from repro.core.state import KMeansState, RoundInfo


# --------------------------------------------------------------------------
# the ONE steady-state device->host crossing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HostRoundInfo:
    """`RoundInfo` landed on the host: plain Python scalars.

    Every per-round decision in `run_loop` branches on THIS object (or
    on the resolved config / engine statics) — never on a live device
    value. The fields are psum-reduced before they leave the round, so
    the same bits land on every process (see the module docstring).
    """
    batch_mse: float
    n_changed: int
    n_recomputed: int
    n_active: int
    overflow: bool
    grow: bool
    r_median: float
    p_max: float


def fetch_round_info(info: RoundInfo) -> HostRoundInfo:
    """Land the round's psum-reduced scalars on the host in ONE transfer.

    This is the single sanctioned device->host read of the steady-state
    loop: everything the schedule branches on crosses here, together,
    once per round. Scattering `float(info.x)` reads through the loop
    body would work too — but then nothing distinguishes a sanctioned
    sync from an accidental one, and the host-sync auditor
    (`repro.analysis.hostsync`) could not scope its guard. Keep new
    device reads OUT of the loop body: extend `RoundInfo` instead and
    read the field off the result of this function.
    """
    host = jax.device_get(info)
    return HostRoundInfo(
        batch_mse=float(host.batch_mse), n_changed=int(host.n_changed),
        n_recomputed=int(host.n_recomputed), n_active=int(host.n_active),
        overflow=bool(host.overflow), grow=bool(host.grow),
        r_median=float(host.r_median), p_max=float(host.p_max))


class LoopAudit:
    """Instrumentation seam for `repro.analysis.hostsync`.

    `run_loop` brackets every round body with ``round_scope()`` and each
    sanctioned device<->host crossing inside it with
    ``sanctioned_scope(what)``, where ``what`` is one of:

      * ``"round_info"`` — the `fetch_round_info` scalar landing;
      * ``"eval_mse"``   — validation eval at the configured cadence;
      * ``"sync_flag"``  — the coordinator's wall-clock broadcast;
      * ``"checkpoint"`` — `run.capture` gathers + store writes.

    The default scopes are no-ops, so production fits pay nothing. The
    host-sync auditor subclasses this to disallow transfers inside the
    round scope and re-allow them inside the sanctioned scopes — any
    OTHER device->host sync in the steady-state loop becomes a
    diagnosable violation instead of a silent stall-per-round.
    """

    def round_scope(self):
        return contextlib.nullcontext()

    def sanctioned_scope(self, what: str):
        return contextlib.nullcontext()


_NULL_AUDIT = LoopAudit()



# --------------------------------------------------------------------------
# result record
# --------------------------------------------------------------------------

@dataclasses.dataclass
class FitOutcome:
    """What a fit produces: centroids + full state + structured telemetry.

    ``labels`` is in the CALLER's row order (the engines shuffle and, on
    a mesh, interleave/pad internally; the inverse mapping is applied
    here). ``-1`` marks rows the nested batch never reached.
    """
    C: np.ndarray
    state: KMeansState
    labels: np.ndarray
    telemetry: List[Telemetry]
    converged: bool
    algorithm: str
    config: FitConfig
    #: the engine's resolved `KernelPlan` as a JSON-safe dict (backend,
    #: block sizes, bucket, tuner provenance); benchmark manifests
    #: record it so "which kernels actually ran" is never a null again
    kernel_plan: Optional[Dict[str, Any]] = None

    @property
    def final_mse(self) -> float:
        return final_val_mse(self.telemetry)


# --------------------------------------------------------------------------
# capacity policy (shared)
# --------------------------------------------------------------------------

def next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def cap_bucket(need: int, b: int, floor: int) -> Optional[int]:
    """Power-of-two capacity with 2x slack; None == recompute everything."""
    cap = max(floor, next_pow2(2 * max(need, 1)))
    return None if cap >= b else cap


# --------------------------------------------------------------------------
# THE shared host loop
# --------------------------------------------------------------------------

def run_loop(run: EngineRun, config: FitConfig, *,
             on_round: Optional[RoundCallback] = None,
             resume_from: Optional[Union[str, Path, CheckpointStore]] = None,
             resolved_resume: Optional[Tuple[int, Dict[str, Any]]] = None,
             trace: Optional[List[Dict[str, Any]]] = None,
             audit: Optional[LoopAudit] = None,
             obs: Optional[ObsSink] = None
             ) -> FitOutcome:
    """Growth schedule + capacity bucketing + overflow retry + patience.

    ``config`` must already be `resolve()`d (no alias algorithms). The
    loop is backend-agnostic AND process-agnostic: see the module
    docstring for the replication invariant that makes the same code
    drive one device, a host mesh, or a multi-process pod.

    When ``config.checkpoint`` is set, the FULL loop state — engine
    state, batch size, capacity bucket, patience counter, work clock and
    telemetry — is saved atomically every ``save_every`` rounds (plus
    once at loop exit) alongside the ``config.to_dict()`` manifest.
    ``resume_from`` (a directory or `CheckpointStore`) restores the
    latest such checkpoint through the engine's canonical layout, so a
    killed fit continues bit-identically — and a fit checkpointed on
    one shard count (or process count) resumes on another (elastic
    restart). ``resolved_resume``: the ``(step, extra)`` pair a caller
    already obtained from ``run.resolve_resume`` on the same store
    (the estimator validates the manifest first); passing it avoids a
    second read — and on multihost a second cluster-wide broadcast —
    of the same payload.

    ``trace``: optional list; one dict per completed round —
    ``{"round", "b_global", "capacity", "quiet_rounds"}`` — is appended
    AFTER the round's schedule updates. This is the loop's control-flow
    fingerprint: two processes of the same multihost fit must produce
    identical traces (scripts/smoke_multihost.py asserts exactly that).

    ``audit``: optional `LoopAudit` whose scopes bracket each round body
    and its sanctioned device<->host crossings (the host-sync auditor's
    hook). ``None`` uses the no-op scopes.

    ``obs``: optional sink receiving each round's host-landed scalars,
    span timings, overflow-retry counts and the count of dispatches
    that take the delta S/v over hamerly2's compacted buffer
    (``round.delta_compacted``; an overflow retry that still compacts
    counts again) — usually a
    `repro.obs.FitObserver`, wrapped by `as_sink` so its spans also
    reach the profiler. ``None`` uses the default sink, whose spans
    reach the profiler alone. The spans: ``round`` (one iteration of
    the round loop) holding ``round.dispatch`` (each step call, again
    on an overflow retry), ``round.wait`` (the device finishing it),
    ``round.info`` (`fetch_round_info`) and ``round.record``
    (telemetry, the sink, ``on_round``; ``eval_mse`` nests in it);
    ``checkpoint``; ``fit.finish`` (final eval, labels, stats). The
    loop does NOT close the sink; its creator does (the estimator
    closes the observer it built from ``config.trace_dir``).
    """
    audit = audit if audit is not None else _NULL_AUDIT
    obs = as_sink(obs)
    algorithm = config.algorithm
    bounds = config.bounds
    state = run.state
    b = run.b
    capacity: Optional[int] = None
    telemetry: List[Telemetry] = []
    t_work = 0.0
    quiet_rounds = 0
    converged = False
    start_round = 0
    timed = math.isfinite(config.time_budget_s)
    run.bind_obs(obs)

    ckpt = config.checkpoint
    store = (CheckpointStore(ckpt.checkpoint_dir, keep=ckpt.keep)
             if ckpt is not None else None)

    if store is not None and resume_from is None:
        # a FRESH checkpointed fit supersedes whatever run lives in the
        # directory: left in place, the old (higher-numbered) steps
        # would garbage-collect this run's early saves on arrival and a
        # later resume would silently restore the stale fit
        if run.is_coordinator and store.latest_step() is not None:
            store.clear()
        run.barrier()

    if resume_from is not None:
        rstore = (resume_from if isinstance(resume_from, CheckpointStore)
                  else CheckpointStore(resume_from,
                                       keep=ckpt.keep if ckpt else 3))
        step, extra = (resolved_resume if resolved_resume is not None
                       else run.resolve_resume(rstore))
        if step is None:
            raise FileNotFoundError(
                f"resume_from={resume_from!r} holds no checkpoints")
        if not extra or "loop" not in extra:
            raise ValueError(
                f"checkpoint step {step} has no loop metadata; it was "
                f"not written by run_loop")
        emeta, loop = extra["engine"], extra["loop"]
        # dataset identity gate: a resume against a DIFFERENT dataset
        # would restore per-point state that describes rows the new data
        # does not have — silently producing garbage labels. Fingerprints
        # are JSON-safe dicts, so old checkpoints (no "data" key) skip
        # the check rather than break.
        saved_fp = extra.get("data")
        fp = getattr(run, "data_fingerprint", None)
        if saved_fp is not None and fp is not None and saved_fp != fp:
            diff = sorted(k for k in set(saved_fp) | set(fp)
                          if saved_fp.get(k) != fp.get(k))
            raise ValueError(
                f"checkpoint step {step} was written for a different "
                f"dataset (fingerprint differs on {diff}: checkpoint "
                f"{saved_fp} vs this fit {fp}); resuming would silently "
                f"mislabel the new data — refusing")
        state = run.restore(rstore, step, emeta)
        telemetry = [Telemetry.from_dict(r) for r in extra["telemetry"]]
        t_work = float(loop["t_work"])
        quiet_rounds = int(loop["quiet_rounds"])
        converged = bool(loop.get("converged", False))
        start_round = int(loop["rounds_done"])
        # b is stored in GLOBAL rows; ceil-divide onto this engine's
        # shard count so every previously-seen point stays inside the
        # prefix when the shard count changed across the restore.
        b = max(1, min(-(-int(loop["b_global"]) // run.n_shards),
                       run.b_max))
        cap = loop.get("capacity")
        capacity = (int(cap) if cap is not None
                    and int(emeta.get("n_shards", 0)) == run.n_shards
                    else None)
        run.barrier()

    def record(hinfo: HostRoundInfo, dt_s: float) -> None:
        val_mse = None
        if len(telemetry) % config.eval_every == 0:
            # validation eval is a sanctioned device->host read (it is
            # outside the paper's timed region, like every eval)
            with audit.sanctioned_scope("eval_mse"), obs.span("eval_mse"):
                val_mse = run.eval_mse(state)
        rec = Telemetry.from_round(hinfo, round=len(telemetry), t=t_work,
                                   val_mse=val_mse)
        telemetry.append(rec)
        # the obs sink sees only already-host-landed values: hinfo, the
        # schedule's own plain-Python scalars, and the engine's host-side
        # store counters — nothing here can add a device->host sync.
        # b/capacity are PRE-update: the values THIS round actually used.
        obs.round_end(rec.round, hinfo, dt_s=dt_s, t_work=t_work,
                      b_global=min(b * run.n_shards, run.n_points),
                      capacity=capacity, quiet_rounds=quiet_rounds,
                      algorithm=algorithm, val_mse=val_mse,
                      store=run.store_metrics())
        if on_round:
            on_round(rec)

    def save_checkpoint() -> None:
        # capture is a collective (it gathers sharded leaves); every
        # process runs it, only the coordinator touches the disk
        tree, emeta = run.capture(state)
        extra = {
            "config": config.to_dict(),
            "data": run.data_fingerprint,
            "engine": emeta,
            "loop": {"rounds_done": len(telemetry),
                     "b_global": b * run.n_shards, "capacity": capacity,
                     "quiet_rounds": quiet_rounds, "t_work": t_work,
                     "converged": converged},
            "telemetry": [r.to_dict() for r in telemetry],
        }
        if run.is_coordinator:
            store.save(len(telemetry), tree, extra=extra,
                       background=ckpt.background)
        run.barrier()

    def step() -> Tuple[KMeansState, HostRoundInfo]:
        """Dispatch one round on ``state``, wait for it, land its
        `RoundInfo`: the round's state and its host scalars."""
        with obs.span("round.dispatch"):
            if algorithm == "lloyd":
                new_state, info = run.lloyd_step(state)
            elif algorithm in ("mb", "mbf"):
                new_state, info = run.mb_step(
                    state, fixed=(algorithm == "mbf"))
            else:  # tb family (incl. gb via bounds="none")
                new_state, info = run.nested_step(state, b, capacity)
                if (bounds == "hamerly2" and capacity is not None
                        and capacity < b and run.delta_over_buffer):
                    obs.count("round.delta_compacted")
        with obs.span("round.wait"):
            jax.block_until_ready(new_state.stats.C)
        with audit.sanctioned_scope("round_info"), obs.span("round.info"):
            return new_state, fetch_round_info(info)

    for _ in range(start_round, config.max_rounds):
        if converged:        # resumed an already-finished fit
            break
        with audit.round_scope(), obs.span("round"):
            if timed:
                # the wall clock is the one host-local input to the
                # schedule: the coordinator decides, every process obeys
                with audit.sanctioned_scope("sync_flag"):
                    out_of_time = run.sync_flag(
                        t_work >= config.time_budget_s)
                if out_of_time:
                    break
            t0 = time.perf_counter()
            new_state, hinfo = step()
            while hinfo.overflow:
                # overflow retry (the lloyd and mb rounds never
                # overflow): same input state, doubled bucket —
                # exactness is never traded for speed.
                obs.count("overflow_retry")
                capacity = (None
                            if capacity is None or 2 * capacity >= b
                            else 2 * capacity)
                new_state, hinfo = step()
            dt_s = time.perf_counter() - t0
            t_work += dt_s
            state = new_state
            with obs.span("round.record"):
                record(hinfo, dt_s)
            if algorithm == "tb":
                if bounds == "hamerly2":
                    need = -(-hinfo.n_recomputed // run.n_shards)
                    if hinfo.grow and b < run.b_max:
                        # a doubling adds b new points that always need
                        # a full pass — start the grown bucket dense
                        capacity = None
                    else:
                        capacity = cap_bucket(need, b,
                                              config.capacity_floor)
                if hinfo.grow:
                    b = min(2 * b, run.b_max)
                # p_max rides along in the psum-consistent RoundInfo —
                # no extra device->host sync outside the timed region
                if (hinfo.n_active >= run.n_active_target
                        and hinfo.n_changed == 0
                        and hinfo.p_max == 0.0):
                    quiet_rounds += 1
                else:
                    quiet_rounds = 0
                if trace is not None:
                    trace.append({"round": len(telemetry) - 1,
                                  "b_global": b * run.n_shards,
                                  "capacity": capacity,
                                  "quiet_rounds": quiet_rounds})
                if quiet_rounds >= config.converge_patience:
                    converged = True
                    break
            elif algorithm == "lloyd":
                if hinfo.n_changed == 0:
                    converged = True
                    break

            if store is not None and len(telemetry) % ckpt.save_every == 0:
                # capture's gathers + the coordinator's disk write are
                # sanctioned crossings (bracketed by run.barrier)
                with audit.sanctioned_scope("checkpoint"), \
                        obs.span("checkpoint"):
                    save_checkpoint()

    with obs.span("fit.finish"):
        if store is not None:
            # one final save so a resumed-after-finish fit is a no-op
            with obs.span("checkpoint"):
                save_checkpoint()
                if run.is_coordinator:
                    store.wait()
            run.barrier()

        # final validation point (outside the timed region, like every
        # eval), unless the last in-loop round already evaluated
        # validation — a second eval at the same t would double-count
        # it in the telemetry
        if telemetry and telemetry[-1].val_mse is not None:
            final = None
        else:
            final = run.eval_mse(state)
        if final is not None:
            # b is per-shard; b * n_shards includes the structural pad
            # rows on a non-divisible mesh, so cap at the real size
            telemetry.append(Telemetry(
                round=len(telemetry), t=t_work,
                b=min(b * run.n_shards, run.n_points),
                batch_mse=None, n_changed=0, n_recomputed=0, grow=False,
                r_median=None, val_mse=final))

        obs.fit_end(rounds=len(telemetry), t_work=t_work,
                    converged=converged, n_shards=run.n_shards)

        # un-shuffle the final assignments back to the caller's row
        # order; host_points is a gather collective on multi-process
        # runs
        a = np.asarray(run.host_points(state))
        labels = np.full(run.n_points, -1, np.int32)
        valid = run.orig_index >= 0
        labels[run.orig_index[valid]] = a[valid]

        C = np.asarray(run.fetch_stats(state).C)
    plan = getattr(run, "kernel_plan", None)
    return FitOutcome(C=C, state=state,
                      labels=labels, telemetry=telemetry,
                      converged=converged, algorithm=algorithm,
                      config=config,
                      kernel_plan=plan.to_dict() if plan else None)
