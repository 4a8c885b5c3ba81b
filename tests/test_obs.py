"""The observability plane: tracer, registry, the instrumented fit loop,
and the ``repro.*`` spans a profiler trace of a fit and a predict shows.

The pure-python tests (tracer nesting/rotation/merge, histogram bounds,
Prometheus export, registry semantics, Telemetry JSON round-trip) need
no jax at all — `repro.obs` imports neither jax nor numpy, and one test
pins that property. The jax tests drive real traced fits: round events
must match the loop's own schedule trace, `telemetry_` must round-trip
through `to_dict`, and the host-sync auditor must stay SILENT with a
`FitObserver` attached. A tiny fit and three predicts run under
`jax.profiler.trace`, with and without ``trace_dir``: the profiler's
host plane holds the same ``repro.*`` spans either way, nested as the
loop runs them. The slow test runs scripts/smoke_obs.py, which repeats
the traced fit + hostsync gate on mesh/xl/multihost.
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

from repro.obs import (OBS_SCHEMA, Histogram, MetricsRegistry,
                       ServeMetrics, SpanTracer, read_events, summarize,
                       trace_files)


# ---------------------------------------------------------------------------
# tracer: nesting, rotation, merge
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs(tmp_path):
    with SpanTracer(tmp_path) as tr:
        with tr.span("outer", phase="warm"):
            tr.event("tick", n=1)
            with tr.span("inner"):
                pass
    ev = read_events(tmp_path)
    by_name = {e.get("name"): e for e in ev if "name" in e}
    outer, inner, tick = by_name["outer"], by_name["inner"], by_name["tick"]
    assert outer["ph"] == "span" and outer["parent"] is None
    assert outer["attrs"] == {"phase": "warm"}
    assert inner["parent"] == outer["id"]
    assert tick["ph"] == "event" and tick["parent"] == outer["id"]
    # spans are written at EXIT but ts is the START offset
    assert inner["ts"] >= outer["ts"]
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0
    assert all(e["schema"] == OBS_SCHEMA for e in ev)


def test_rotation_and_merged_order(tmp_path):
    with SpanTracer(tmp_path, rotate_bytes=4096) as tr:
        for i in range(300):
            tr.event("e", i=i, pad="x" * 40)
    files = trace_files(tmp_path)
    assert len(files) > 1, "4096-byte rotation never triggered"
    ev = [e for e in read_events(tmp_path) if e.get("name") == "e"]
    assert [e["attrs"]["i"] for e in ev] == list(range(300))


def test_multiprocess_merge_and_filter(tmp_path):
    for pid in (0, 1):
        with SpanTracer(tmp_path, process_id=pid) as tr:
            for r in range(3):
                tr.event("round", round=r, kscans=10, dt_s=0.5)
    ev = read_events(tmp_path)
    assert {e["pid"] for e in ev} == {0, 1}
    only0 = read_events(tmp_path, process_id=0)
    assert {e["pid"] for e in only0} == {0}
    s = summarize(ev)
    assert s["processes"] == [0, 1]
    assert s["rounds_by_process"] == {0: 3, 1: 3}
    # round scalars come from the lead process ONLY (RoundInfo is
    # psum-reduced — summing across processes would double-count)
    assert s["rounds"] == 3 and s["kscans_total"] == 30


def test_reader_is_loud(tmp_path):
    with pytest.raises(FileNotFoundError):
        read_events(tmp_path)
    with SpanTracer(tmp_path) as tr:
        tr.event("ok")
    f = trace_files(tmp_path)[0]
    with open(f, "a", encoding="utf-8") as fh:
        fh.write('{"schema": 999, "ph": "event"}\n')
    with pytest.raises(ValueError, match="newer"):
        read_events(tmp_path)
    with open(f, "w", encoding="utf-8") as fh:
        fh.write("not json\n")
    with pytest.raises(ValueError, match="corrupt"):
        read_events(tmp_path)


def test_tracer_survives_numpy_scalars(tmp_path):
    np = pytest.importorskip("numpy")
    with SpanTracer(tmp_path) as tr:
        tr.event("e", a=np.int64(3), b=np.float32(0.5))
    e = [x for x in read_events(tmp_path) if x.get("name") == "e"][0]
    assert e["attrs"]["a"] == 3
    assert abs(e["attrs"]["b"] - 0.5) < 1e-9


# ---------------------------------------------------------------------------
# metrics: histogram bounds, registry, exporters
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_factor():
    h = Histogram("t")
    vals = [i / 1000.0 for i in range(1, 1001)]     # 1ms .. 1s uniform
    for v in vals:
        h.record(v)
    for q in (0.50, 0.99):
        true = vals[int(q * (len(vals) - 1))]
        est = h.percentile(q)
        assert true <= est <= true * Histogram.BASE * 1.001, (q, est, true)
    d = h.to_dict()
    assert d["count"] == 1000 and d["max_s"] == 1.0
    assert abs(d["mean_s"] - sum(vals) / 1000) < 1e-9
    assert set(d) == {"count", "mean_s", "p50_s", "p99_s", "max_s"}


def test_registry_semantics():
    r = MetricsRegistry()
    c = r.counter("c", "help")
    assert r.counter("c") is c                      # get-or-create
    with pytest.raises(ValueError, match="Counter"):
        r.gauge("c")
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    c.inc(2)
    r.gauge("g").set(1.5)
    r.histogram("h").record(0.25)
    d = r.to_dict()
    assert d["counters"]["c"] == 2
    assert d["gauges"]["g"] == 1.5
    assert d["histograms"]["h"]["count"] == 1


def test_prometheus_export_format():
    r = MetricsRegistry()
    r.counter("fit rounds", "completed rounds").inc(3)
    r.gauge("util").set(0.5)
    h = r.histogram("lat", "latency")
    for v in (0.001, 0.01, 0.01, 0.1):
        h.record(v)
    text = r.to_prometheus()
    assert "# TYPE fit_rounds counter\nfit_rounds 3" in text
    assert "# TYPE util gauge\nutil 0.5" in text
    assert "# HELP fit_rounds completed rounds" in text
    # histogram buckets are CUMULATIVE and +Inf equals the total count
    counts = [int(m) for m in
              re.findall(r'lat_bucket\{le="[^"]+"\} (\d+)', text)]
    assert counts == sorted(counts)
    assert counts[-1] == 4 and 'le="+Inf"' in text
    assert "lat_count 4" in text
    assert every_line_parses(text)


def every_line_parses(text):
    pat = re.compile(r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
                     r'|[a-zA-Z_:][a-zA-Z0-9_:]*(_bucket\{le="[^"]+"\})? '
                     r"[0-9eE.+-]+|[a-zA-Z_:][a-zA-Z0-9_:]* NaN)$")
    return all(pat.match(line) for line in text.splitlines())


def test_serve_metrics_schema_byte_compatible():
    m = ServeMetrics()
    m.observe_predict(0.002, 128)
    m.observe_refresh(0.050, 256)
    m.observe_escalation()
    m.observe_ingest()
    d = m.to_dict(queue_stats={"rows": 1, "dropped": 0})
    assert set(d) == {"predict", "refresh", "ingest_calls", "queue"}
    assert set(d["predict"]) == {"requests", "rows", "latency"}
    assert set(d["refresh"]) == {"count", "rows", "escalations", "latency"}
    assert set(d["predict"]["latency"]) == {"count", "mean_s", "p50_s",
                                            "p99_s", "max_s"}
    assert d["predict"] == {"requests": 1, "rows": 128,
                            "latency": m.predict_latency.to_dict()}
    assert d["refresh"]["count"] == 1 and d["refresh"]["escalations"] == 1
    assert d["ingest_calls"] == 1
    json.dumps(d)                                   # JSON-safe
    # the legacy import path still resolves to the same classes
    from repro.serve.metrics import ServeMetrics as Legacy
    assert Legacy is ServeMetrics


def test_obs_package_is_accelerator_free():
    code = ("import sys, repro.obs, repro.obs.sink, repro.obs.__main__; "
            "bad = [m for m in ('jax', 'numpy') if m in sys.modules]; "
            "assert not bad, bad; print('clean')")
    env = dict(os.environ, PYTHONPATH="src")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "clean" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Telemetry round-trip
# ---------------------------------------------------------------------------

def test_telemetry_json_roundtrip_nonfinite():
    from repro.api.telemetry import Telemetry
    rec = Telemetry(round=3, t=1.5, b=256, batch_mse=float("nan"),
                    n_changed=2, n_recomputed=100, grow=True,
                    r_median=float("inf"), val_mse=None)
    d = rec.to_dict()
    assert d["batch_mse"] == "nan" and d["r_median"] == "inf"
    text = json.dumps(d)                # strict-parser safe
    back = Telemetry.from_dict(json.loads(text))
    assert math.isnan(back.batch_mse) and math.isinf(back.r_median)
    assert back.round == 3 and back.b == 256 and back.val_mse is None
    finite = Telemetry(round=0, t=0.1, b=8, batch_mse=2.0, n_changed=1,
                       n_recomputed=8, grow=False, r_median=0.5,
                       val_mse=3.0)
    assert Telemetry.from_dict(
        json.loads(json.dumps(finite.to_dict()))) == finite


# ---------------------------------------------------------------------------
# the instrumented fit (local backend; the smoke covers the rest)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["host", "v5e"])
def traced_fit(request, tmp_path_factory):
    """A traced local fit. ``host``: the observer gets this process's
    own device_kind (the CPU). ``v5e``: the test hands it the v5e's,
    which it records as it is."""
    import jax
    import numpy as np

    from repro.api.config import FitConfig
    from repro.api.engines import make_engine
    from repro.api.loop import run_loop
    from repro.obs import FitObserver
    from repro.roofline.analysis import V5E

    td = tmp_path_factory.mktemp("trace")
    rng = np.random.default_rng(0)
    n, d, k = 4096, 16, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    X_val = rng.normal(size=(512, d)).astype(np.float32)
    config = FitConfig(k=k, b0=256, seed=0, max_rounds=20,
                       eval_every=4, capacity_floor=32).resolve(n)
    run = make_engine(config).begin(X, config, X_val=X_val)
    schedule = []
    kind = (V5E if request.param == "v5e"
            else jax.devices()[0].device_kind)
    with FitObserver(td, k=k, d=d, device_kind=kind,
                     meta={"backend": "local"}) as obs:
        out = run_loop(run, config, trace=schedule, obs=obs)
    return td, out, schedule, kind


def test_round_events_match_schedule_trace(traced_fit):
    td, out, schedule, kind = traced_fit
    ev = read_events(td)
    # the "round" events; the "round" spans around them hold the stages
    rounds = [e for e in ev
              if e.get("ph") == "event" and e.get("name") == "round"]
    assert len(rounds) == len(schedule) > 0
    for e, s in zip(rounds, schedule):
        assert e["attrs"]["round"] == s["round"]
        assert e["attrs"]["quiet_rounds"] == s["quiet_rounds"]
    s = summarize(ev)
    assert s["rounds"] == len(schedule)
    assert s["kscans_total"] == sum(r.n_recomputed for r in out.telemetry)
    start = next(e for e in ev if e.get("name") == "fit_start")
    assert start["attrs"]["device_kind"] == kind
    # rounds carry the host-landed scalars, and no priced work
    assert all(e["attrs"]["kscans"] >= 0 for e in rounds)
    assert not {"flops", "bytes", "utilization"} & {
        key for e in rounds for key in e["attrs"]}
    names = {e.get("name") for e in ev}
    assert {"fit_start", "fit_end", "round"} <= names


def test_metrics_json_written_at_close(traced_fit):
    td, out, schedule, kind = traced_fit
    path = td / "metrics-p00000.json"
    m = json.loads(path.read_text())
    assert m["counters"]["fit_rounds"] == len(schedule)
    assert m["counters"]["fit_kscans"] == sum(
        r.n_recomputed for r in out.telemetry)
    assert m["histograms"]["fit_round_seconds"]["count"] == len(schedule)
    # the one gauge is the batch size, a host-landed value
    assert set(m["gauges"]) == {"fit_b_global"}
    # every span of the fit is timed under its own name: one info
    # landing a round, and one more for each overflow retry
    assert m["histograms"]["fit_span_round.info_seconds"]["count"] == (
        len(schedule) + m["counters"].get("fit_overflow_retry", 0))


@pytest.mark.parametrize("bounds", ["hamerly2", "none"])
def test_compacted_delta_rounds_are_counted(tmp_path, blobs, bounds):
    """Each dispatch of a hamerly2 round whose capacity is below its
    batch takes the delta S/v over the compacted buffer, and counts
    once: the rounds whose events show ``capacity < b``, and each
    overflow retry (only a compacted dispatch overflows). A fit without
    hamerly2 counts none."""
    from repro.api.config import FitConfig
    from repro.api.engines import make_engine
    from repro.api.loop import run_loop
    from repro.obs import FitObserver

    X, _ = blobs
    n, d = X.shape
    config = FitConfig(k=8, b0=256, seed=0, max_rounds=30, bounds=bounds,
                       capacity_floor=32).resolve(n)
    run = make_engine(config).begin(X, config)
    with FitObserver(tmp_path, k=8, d=d,
                     meta={"backend": "local"}) as obs:
        run_loop(run, config, obs=obs)
    counters = json.loads(
        (tmp_path / "metrics-p00000.json").read_text())["counters"]
    rounds = [e["attrs"] for e in read_events(tmp_path)
              if e.get("ph") == "event" and e.get("name") == "round"]
    compacted = sum(r["capacity"] is not None
                    and r["capacity"] < r["b_global"] for r in rounds)
    assert (compacted > 0) == (bounds == "hamerly2")
    assert counters.get("fit_round.delta_compacted", 0) == (
        compacted + counters.get("fit_overflow_retry", 0))


def test_estimator_telemetry_roundtrip(tmp_path, blobs, blobs_val):
    import dataclasses

    from repro.api import FitConfig, NestedKMeans, Telemetry
    X, _ = blobs
    cfg = FitConfig(k=8, b0=256, seed=0, max_rounds=12,
                    trace_dir=str(tmp_path / "tr"))
    km = NestedKMeans(cfg).fit(X, X_val=blobs_val)
    assert km.telemetry_
    for rec in km.telemetry_:
        back = Telemetry.from_dict(json.loads(json.dumps(rec.to_dict())))
        assert dataclasses.asdict(back) == dataclasses.asdict(rec)
    # partial_fit extends telemetry through the SAME record builder
    n0 = len(km.telemetry_)
    km.partial_fit(X[:256])
    rec = km.telemetry_[-1]
    assert len(km.telemetry_) == n0 + 1 and rec.round == n0
    assert rec.b == 256 and rec.batch_mse is not None
    # the traced fit wrote a parseable event log
    assert summarize(read_events(tmp_path / "tr"))["rounds"] > 0


def test_fitconfig_trace_dir_validation():
    from repro.api import FitConfig
    with pytest.raises(ValueError, match="trace_dir"):
        FitConfig(k=8, trace_dir="")
    d = FitConfig(k=8, trace_dir="/tmp/x").to_dict()
    assert d["trace_dir"] == "/tmp/x"
    from repro.api.config import FitConfig as FC
    assert FC.from_dict(d).trace_dir == "/tmp/x"


def test_hostsync_silent_with_tracing_on(tmp_path):
    """The acceptance gate: a FitObserver attached to an audited fit
    adds ZERO unsanctioned device->host syncs."""
    from repro.analysis import hostsync
    found = hostsync.audit_backend(backend="local",
                                   trace_dir=str(tmp_path))
    assert found == []
    assert summarize(read_events(tmp_path))["rounds"] > 0


# ---------------------------------------------------------------------------
# the repro.* spans on the profiler's host plane
# ---------------------------------------------------------------------------

SINKS = ["default", "trace_dir"]
SET_UP = ["repro.fit.shuffle", "repro.fit.to_device", "repro.fit.init"]
ROUND = ["repro.round.dispatch", "repro.round.wait", "repro.round.info",
         "repro.round.record"]
PREDICT = ["repro.predict.put", "repro.predict.dispatch",
           "repro.predict.fetch"]


def _repro_spans(log_dir):
    """``repro.*`` events of the trace's host plane: (name, start_ns,
    end_ns), in time order."""
    from pathlib import Path

    from jax.profiler import ProfileData
    xplane, = Path(log_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(outer, spans, name=None):
    """The spans (called ``name``) that lie in time inside ``outer``."""
    _, lo, hi = outer
    return [e for e in spans if e is not outer and lo <= e[1]
            and e[2] <= hi and (name is None or e[0] == name)]


@pytest.fixture(scope="module")
def profiles(tmp_path_factory):
    """A tiny tb/hamerly2 fit and 3 predicts under `jax.profiler.trace`,
    once with the default sink and once with ``trace_dir`` set:
    ``{sink: (repro.* spans, the fit's telemetry, trace_dir)}``."""
    import jax
    import numpy as np

    from repro.api import FitConfig, NestedKMeans
    from repro.serve import CodebookSnapshot

    X = np.random.default_rng(0).normal(size=(2048, 16)).astype(np.float32)
    base = dict(k=8, algorithm="tb", bounds="hamerly2", b0=256, seed=0,
                max_rounds=12, capacity_floor=32)
    NestedKMeans(FitConfig(**base)).fit(X)       # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = {}
    for sink in SINKS:
        td = (str(tmp_path_factory.mktemp("jsonl"))
              if sink == "trace_dir" else None)
        km = NestedKMeans(FitConfig(**base, trace_dir=td))
        log_dir = tmp_path_factory.mktemp(f"profile-{sink}")
        with jax.profiler.trace(str(log_dir), profiler_options=opts):
            km.fit(X)
            snap = CodebookSnapshot.create(1, km.export_codebook())
            for i in range(3):
                snap.predict(X[16 * i:16 * (i + 1)])
        out[sink] = (_repro_spans(log_dir), km.telemetry_, td)
    return out


@pytest.mark.parametrize("sink", SINKS)
def test_profiler_shows_the_fit_set_up_and_finish(profiles, sink):
    spans, telemetry, _ = profiles[sink]
    begin, = [e for e in spans if e[0] == "repro.fit.begin"]
    inner = _inside(begin, spans)
    assert [e[0] for e in inner] == SET_UP
    finish, = [e for e in spans if e[0] == "repro.fit.finish"]
    last_round = [e for e in spans if e[0] == "repro.round"][-1]
    assert begin[2] <= last_round[1] and last_round[2] <= finish[1]


@pytest.mark.parametrize("sink", SINKS)
def test_profiler_shows_each_round_and_its_stages(profiles, sink):
    spans, telemetry, _ = profiles[sink]
    rounds = [e for e in spans if e[0] == "repro.round"]
    assert len(rounds) == len(telemetry) > 1
    for r in rounds:
        assert len(_inside(r, spans, "repro.round.info")) == 1
        stages = [e[0] for e in _inside(r, spans) if e[0] in ROUND]
        assert stages == ROUND


@pytest.mark.parametrize("sink", SINKS)
def test_profiler_shows_each_predict_and_its_stages(profiles, sink):
    spans, _, _ = profiles[sink]
    predicts = [e for e in spans if e[0] == "repro.predict"]
    assert len(predicts) == 3
    for p in predicts:
        assert [e[0] for e in _inside(p, spans)] == PREDICT


def test_profiler_names_are_the_same_with_and_without_trace_dir(profiles):
    names = {sink: sorted({e[0] for e in profiles[sink][0]})
             for sink in SINKS}
    assert names["default"] == names["trace_dir"]
    assert {"repro.fit.begin", "repro.round", "repro.fit.finish",
            "repro.predict"} <= set(names["default"])


def test_jsonl_holds_the_fit_spans_under_the_same_names(profiles):
    spans, _, td = profiles["trace_dir"]
    jsonl = [e["name"] for e in read_events(td) if e.get("ph") == "span"]
    fit = [e[0][len("repro."):] for e in spans
           if not e[0].startswith("repro.predict")]
    assert sorted(jsonl) == sorted(fit)


def test_cli_summarize_and_tail(tmp_path, capsys):
    from repro.obs.__main__ import main
    with SpanTracer(tmp_path) as tr:
        tr.event("round", round=0, kscans=5, dt_s=0.1)
    assert main(["summarize", str(tmp_path)]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["rounds"] == 1 and s["kscans_total"] == 5
    assert main(["tail", str(tmp_path), "-n", "1"]) == 0
    line = capsys.readouterr().out.strip()
    assert json.loads(line)["name"] == "round"
    assert main(["summarize", str(tmp_path / "nope")]) == 2


# ---------------------------------------------------------------------------
# the full stack (every backend, forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_obs_smoke_subprocess():
    """scripts/smoke_obs.py: traced fits on local/mesh/xl/multihost with
    round events == schedule trace, plus lint + hostsync with tracing
    on every backend."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "scripts/smoke_obs.py"],
                       env=env, capture_output=True, text=True,
                       timeout=600, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("local: rounds=", "mesh: rounds=", "xl: rounds=",
                   "multihost: rounds=", "replicated lint: clean",
                   "multihost: hostsync clean with tracing on",
                   "obs smoke OK"):
        assert marker in r.stdout, f"missing {marker!r}:\n{r.stdout}"
