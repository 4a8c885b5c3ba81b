"""Dev smoke: the observability plane end to end, on every backend.

Run via subprocess (forces 4 host devices before jax initialises):

    PYTHONPATH=src python scripts/smoke_obs.py

Per backend (local / mesh / xl / multihost single-process) this drives
one TRACED fit through `run_loop` with a schedule-trace list attached
and asserts the two sides agree:

  * the trace directory parses (`repro.obs.read_events`) and its
    per-round "round" events are exactly the in-loop rounds — one per
    entry of the loop's own schedule trace (the control-flow
    fingerprint `scripts/smoke_multihost.py` compares across
    processes);
  * `summarize` aggregates them (rounds, k-scans, span timings);
  * the k-scan total equals the telemetry's `n_recomputed` sum.

Then the invariant checkers run over the INSTRUMENTED loop:

  * the replicated-control-flow AST lint stays clean;
  * the host-sync auditor stays clean on all four backends WITH a
    FitObserver attached (`hostsync.audit_backend(trace_dir=...)`) —
    tracing adds zero unsanctioned device->host syncs.
"""
from repro.util.env import force_host_device_count
force_host_device_count(4)

import tempfile

import numpy as np

BACKENDS = ("local", "mesh", "xl", "multihost")


def traced_fit(backend: str, trace_dir: str):
    import jax

    from repro.analysis.retrace import _mesh_for
    from repro.api.config import FitConfig
    from repro.api.engines import make_engine
    from repro.api.loop import run_loop
    from repro.obs import FitObserver

    rng = np.random.default_rng(0)
    n, d, k = 4096, 16, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    X_val = rng.normal(size=(512, d)).astype(np.float32)
    config = FitConfig(k=k, b0=256, seed=0, backend=backend,
                       max_rounds=24, eval_every=4,
                       capacity_floor=32).resolve(n)
    engine = make_engine(config, mesh=_mesh_for(backend, config))
    run = engine.begin(X, config, X_val=X_val)
    obs = FitObserver(trace_dir, process_id=jax.process_index(),
                      k=k, d=d, device_kind=jax.devices()[0].device_kind,
                      meta={"backend": backend, "smoke": "obs"})
    schedule = []
    try:
        out = run_loop(run, config, trace=schedule, obs=obs)
    finally:
        obs.close()
    return out, schedule


def main():
    from repro.obs import read_events, summarize

    for backend in BACKENDS:
        td = tempfile.mkdtemp(prefix=f"smoke-obs-{backend}-")
        out, schedule = traced_fit(backend, td)
        events = read_events(td)
        rounds = [e for e in events if e.get("ph") == "event"
                  and e.get("name") == "round"]
        # tb fits append one schedule-trace entry per in-loop round,
        # and the observer emits one "round" event per in-loop round:
        # the two independently-built records must agree exactly
        assert len(rounds) == len(schedule), \
            f"{backend}: {len(rounds)} round events vs " \
            f"{len(schedule)} schedule-trace entries"
        s = summarize(events)
        assert s["rounds"] == len(schedule), (backend, s["rounds"])
        kscans_tel = sum(r.n_recomputed for r in out.telemetry)
        assert s["kscans_total"] == kscans_tel, \
            f"{backend}: obs kscans {s['kscans_total']} vs " \
            f"telemetry {kscans_tel}"
        assert s["spans"], f"{backend}: no span timings recorded"
        print(f"{backend}: rounds={s['rounds']} "
              f"kscans={s['kscans_total']} "
              f"jit_traces={s['jit_traces']} "
              f"round_s_total={s['round_s_total']:.3f} "
              f"spans={sorted(s['spans'])}")

    from repro.analysis import replicated_lint
    violations = replicated_lint.run()
    assert not violations, \
        f"replicated lint on the instrumented loop: {violations}"
    print("replicated lint: clean")

    from repro.analysis import hostsync
    for backend in BACKENDS:
        td = tempfile.mkdtemp(prefix=f"smoke-obs-hs-{backend}-")
        found = hostsync.audit_backend(backend=backend, trace_dir=td)
        assert not found, f"{backend} hostsync with tracing on: {found}"
        n_ev = len(read_events(td))
        assert n_ev > 0, f"{backend}: audited fit wrote no events"
        print(f"{backend}: hostsync clean with tracing on "
              f"({n_ev} events)")

    print("obs smoke OK")


if __name__ == "__main__":
    main()
