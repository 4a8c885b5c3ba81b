"""The `Engine` / `EngineRun` contract every backend implements.

An `Engine` owns data placement and compiled rounds; `EngineRun` is one
fit in flight. The host loop (`repro.api.loop.run_loop`) is written
against this contract only — it never imports a concrete engine — and
every quantity it branches on is either a static from the resolved
`FitConfig` or a device-computed scalar out of `RoundInfo`.

Process awareness: a run may span several OS processes (the multihost
engine). The base class defines the process hooks as single-process
no-ops so the local/mesh/xl engines pay nothing; `_MultiHostRun`
overrides them with `jax.distributed` collectives. The contract each
hook must honour is documented on the hook — the loop's correctness on
a pod rests on these contracts, not on the loop's own code.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import FitConfig
from repro.core.state import ClusterStats, KMeansState, RoundInfo


def profiler_span(name: str):
    """The profiler's annotation of program region ``name``.

    Opens ``repro.<name>`` (``repro.<layer>[.<stage>]``) on the host
    plane of any `jax.profiler` trace, on the profiler's own clock, so
    the region lies over the device ops it waits for. With no trace
    running it costs about a microsecond. Carries no attributes: a
    `TraceAnnotation` with keyword arguments costs half as much again
    even when the profiler is off.
    """
    return jax.profiler.TraceAnnotation("repro." + name)


class ObsSink:
    """Observability seam for `repro.obs` — sibling of `LoopAudit`.

    `run_loop` hands every completed round's HOST-landed scalars (the
    `HostRoundInfo`, the schedule's b/capacity/patience values, the
    work-clock delta, the data-store read counters) to ``round_end``,
    brackets every stage of a fit (the engine's set-up, each round's
    dispatch / wait / info / record, eval, checkpoint, store ingest)
    with ``span``, and notes overflow retries with ``count``.

    The base class writes nothing: its ``span`` opens the profiler's
    annotation (`profiler_span`) and the rest are no-ops, so an
    untraced fit pays a few method calls per ROUND — nothing per point,
    and nothing on a device — and any profiler trace of it shows its
    stages. A subclass that overrides ``span`` keeps that by opening
    ``super().span(...)``. The JSONL implementation is
    `repro.obs.FitObserver`, which this seam deliberately does not
    import: `as_sink` wraps it in `ProfiledSink`. Observers consume only
    values that already crossed at a sanctioned point, so
    instrumentation can never add a device->host sync — the hostsync
    auditor runs with tracing ON to prove it.
    """

    def span(self, name: str, **attrs):
        return profiler_span(name)

    def count(self, name: str, n: int = 1) -> None:
        pass

    def round_end(self, round: int, hinfo: Any, **attrs) -> None:
        pass

    def fit_end(self, **summary) -> None:
        pass

    def close(self) -> None:
        pass


class ProfiledSink(ObsSink):
    """A duck-typed sink (`repro.obs.FitObserver`) whose spans also
    open the profiler's annotation, around its own, under one name."""

    def __init__(self, sink: Any):
        self.sink = sink

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        with profiler_span(name), self.sink.span(name, **attrs):
            yield

    def count(self, name: str, n: int = 1) -> None:
        self.sink.count(name, n)

    def round_end(self, round: int, hinfo: Any, **attrs) -> None:
        self.sink.round_end(round, hinfo, **attrs)

    def fit_end(self, **summary) -> None:
        self.sink.fit_end(**summary)

    def close(self) -> None:
        self.sink.close()


NULL_OBS = ObsSink()


def as_sink(obs: Any) -> ObsSink:
    """``obs`` as the loop and the engines call it: the profiler-only
    `NULL_OBS` for None, an `ObsSink` as it is, any other sink wrapped
    in `ProfiledSink`."""
    if obs is None:
        return NULL_OBS
    return obs if isinstance(obs, ObsSink) else ProfiledSink(obs)


class EngineRun:
    """One fit in flight: placed data + initial state + round executors.

    Subclasses set:
      state            initial KMeansState (already placed/sharded)
      b                initial batch size in ENGINE UNITS (global rows
                       for LocalEngine, per-shard rows for MeshEngine)
      b_max            largest batch in engine units
      n_shards         data shards (1 for local)
      n_active_target  info.n_active value meaning "full data active"
      orig_index       (n_storage,) int: original caller row held at
                       each internal storage row (-1 = structural pad)
      n_points         caller's dataset size (pads excluded)
      data_fingerprint JSON-safe content identity of the fitted dataset
                       (`repro.data.store.dataset_fingerprint`); written
                       into checkpoint extras so a resume against a
                       different dataset fails loudly. None disables
                       the check.
    """
    state: KMeansState
    b: int
    b_max: int
    n_shards: int = 1
    n_active_target: int = 0
    orig_index: np.ndarray = None
    n_points: int = 0
    data_fingerprint: Optional[Dict[str, Any]] = None
    #: the fit's resolved `repro.kernels.plan.KernelPlan` (None only for
    #: engines predating the dispatch plane); surfaced in `FitOutcome`
    #: and the benchmark manifests.
    kernel_plan: Optional[Any] = None
    #: whether ``nested_step`` takes a compacted hamerly2 round's delta
    #: S/v over the compacted buffer alone (`core.rounds.nested_round`);
    #: `run_loop` counts those rounds as ``round.delta_compacted``.
    delta_over_buffer: bool = True

    # -- round executors (pure: state in -> (state, info)) ------------------

    def nested_step(self, state: KMeansState, b: int,
                    capacity: Optional[int]
                    ) -> Tuple[KMeansState, RoundInfo]:
        raise NotImplementedError(
            f"{type(self).__name__} does not run the nested family")

    def lloyd_step(self, state: KMeansState
                   ) -> Tuple[KMeansState, RoundInfo]:
        raise NotImplementedError(
            f"{type(self).__name__} does not run lloyd")

    def mb_step(self, state: KMeansState, fixed: bool
                ) -> Tuple[KMeansState, RoundInfo]:
        raise NotImplementedError(
            f"{type(self).__name__} does not run mb/mbf")

    def eval_mse(self, state: KMeansState) -> Optional[float]:
        """Validation MSE of the current centroids (None: no val set).

        Multi-process contract: must return the SAME float on every
        process (the loop's eval cadence and telemetry feed off it).
        """
        return None

    # -- observability (see repro.obs; default: no-ops) ---------------------

    #: the bound obs sink; engine bodies call ``self._obs.span(...)`` /
    #: ``self._obs.count(...)`` unconditionally — the default sink
    #: writes nothing and only opens the profiler's annotation.
    _obs: ObsSink = NULL_OBS

    def bind_obs(self, obs: Any) -> None:
        """Attach the fit's obs sink (by `Engine.begin`, and again by
        `run_loop` before round 0). The sink must only ever be handed
        HOST values — an engine must never pass it a live device array
        (the hostsync auditor enforces this on instrumented fits)."""
        self._obs = as_sink(obs)

    def store_metrics(self) -> Optional[Dict[str, Any]]:
        """Cumulative `repro.data.store` read metrics as a JSON-safe
        dict, or None when this run is not store-backed. Host-side
        counters only — reading them must not touch a device."""
        return None

    # -- host-side views of device state ------------------------------------

    def host_points(self, state: KMeansState) -> np.ndarray:
        """The (n_storage,) assignment vector on the host.

        Multi-process contract: a collective — every process calls it at
        the same loop point and receives the full vector.
        """
        return np.asarray(state.points.a)

    def fetch_stats(self, state: KMeansState) -> ClusterStats:
        """Cluster stats usable from THIS process (host or local device).

        The default hands back the state's own stats leaves (fully
        addressable on every single-process engine). Multi-process runs
        override with a gather so `predict`/`export_codebook` on the
        estimator never touch non-addressable shards.
        """
        return state.stats

    def place_stats(self, state: KMeansState,
                    stats: ClusterStats) -> KMeansState:
        """Return ``state`` with ``stats`` placed in this engine's layout
        (replicated / k-sharded / process-spanning as the engine needs).
        The streaming path (`NestedKMeans.partial_fit`) uses this to
        carry the running statistics into a freshly placed batch run."""
        return dataclasses.replace(
            state, stats=jax.tree.map(jnp.asarray, stats))

    # -- checkpointing (canonical = global-shuffle row order) ---------------

    def capture(self, state: KMeansState) -> Tuple[Dict[str, Any],
                                                   Dict[str, Any]]:
        """(host pytree, JSON-safe engine meta) for a checkpoint.

        Per-point arrays are returned in CANONICAL order — the position
        of each real row in the seed-determined global shuffle, pads
        dropped. The canonical layout depends only on (seed, N_real), so
        a checkpoint written by any engine at any shard count restores
        onto any other (elastic restart).

        Multi-process contract: a collective (it gathers sharded
        leaves); every process calls it, only the coordinator writes the
        result to disk.
        """
        raise NotImplementedError

    def restore(self, store: Any, step: int,
                meta: Dict[str, Any]) -> KMeansState:
        """Rebuild an engine-layout state from a canonical checkpoint.

        Multi-process contract: the coordinator reads the arrays and
        broadcasts them; every process places the SAME canonical values
        into its local shards.
        """
        raise NotImplementedError

    # -- process awareness (single-process defaults) ------------------------
    #
    # The loop derives every per-round decision from shard-replicated
    # RoundInfo scalars, so its control flow is already bit-identical on
    # every process BY CONSTRUCTION. These hooks cover the residue: who
    # writes checkpoints, how processes agree on host-only facts (the
    # wall clock, what is on disk), and rendezvous points.

    #: True on the process allowed to touch the checkpoint directory.
    is_coordinator: bool = True

    def barrier(self) -> None:
        """Block until every process reaches this point (no-op single
        process). The loop calls it around checkpoint writes so no
        process races ahead of a save/clear it may later depend on."""

    def sync_flag(self, flag: bool) -> bool:
        """Replicate a HOST-derived boolean from the coordinator.

        The one loop decision not derivable from device scalars is the
        wall-clock budget (`time_budget_s`): clocks drift between
        processes, so each round the coordinator's verdict is broadcast
        and every process obeys it. Single-process: identity.
        """
        return bool(flag)

    def resolve_resume(self, store: Any
                       ) -> Tuple[Optional[int], Optional[Dict[str, Any]]]:
        """(latest step, its ``extra`` dict) — replicated across
        processes. ``(None, None)`` when the store holds no checkpoints.
        Multi-process runs read on the coordinator and broadcast, so a
        resume decision can never diverge on an eventually-consistent
        filesystem."""
        step = store.latest_step()
        if step is None:
            return None, None
        return step, store.read_extra(step)


@runtime_checkable
class Engine(Protocol):
    """An execution backend: owns data placement + compiled rounds."""

    def begin(self, X, config: FitConfig, *,
              X_val=None, init_C: Optional[np.ndarray] = None,
              obs: Any = None) -> EngineRun:
        """Shuffle/pad/place ``X`` and build the initial state. The run
        is bound to ``obs``, the fit's sink; the local engine binds it
        first, so its set-up stages (``fit.shuffle``, ``fit.to_device``,
        ``fit.init``) reach it."""
        ...
