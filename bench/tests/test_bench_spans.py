"""The readers of the program's ``repro.*`` spans, on hand-made events:
each reads its value from the spans inside the window, leaves out those
outside it, and reads nothing from a trace without them (an older
program's)."""
from types import SimpleNamespace

import pytest

import bench_tiny
from bench.lib.registry import load_module
from bench.lib.trace import Event, Trace, device_op

METRICS = bench_tiny.ROOT / "bench" / "metrics"
_spans = load_module(METRICS / "_spans.py")
WINDOW = (1_000, 20_000)


def _fit_and_predict(shift=0):
    """One fit's set-up, two rounds and two requests, ``shift`` ns
    later: (device ops, host spans)."""
    def ev(name, a, b):
        return Event(name, a + shift, b + shift, "python")

    def op(a, b):
        return device_op("%fusion.1 = f32[] fusion()", a + shift,
                         b + shift, "0")
    host = [ev("repro.fit.begin", 1_100, 2_100),
            ev("repro.fit.shuffle", 1_100, 1_500),
            ev("repro.round", 2_200, 3_200),
            ev("repro.round.info", 3_000, 3_100),
            ev("repro.round", 3_300, 4_300),
            ev("repro.round.info", 4_100, 4_250),
            ev("repro.predict.put", 5_000, 5_300),
            ev("repro.predict.fetch", 5_400, 5_500),
            ev("repro.predict.put", 6_000, 6_100),
            ev("repro.predict.fetch", 6_200, 6_500)]
    # round 1: 500 ns busy; round 2: two overlapping ops, 400 ns busy
    ops = [op(2_300, 2_800), op(3_400, 3_600), op(3_550, 3_800),
           op(5_350, 5_390)]
    return ops, host


# what each reads from the spans of `_fit_and_predict`, in ms
EXPECTED = {
    "ingest_ms_per_fit": 1_000e-6,
    "loop_host_ms_per_round": ((1_000 - 500) + (1_000 - 400)) / 2 * 1e-6,
    "round_info_ms_per_round": (100 + 150) / 2 * 1e-6,
    "predict_put_ms": (300 + 100) / 2 * 1e-6,
    "predict_fetch_ms": (100 + 300) / 2 * 1e-6,
}


def _read(metric, ops, host):
    window = [Event("bench.window", *WINDOW, "python")]
    obs = SimpleNamespace(trace=Trace(ops, host + window), window=WINDOW)
    return load_module(METRICS / f"{metric}.py").read(obs)


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_reads_the_spans_in_the_window(metric):
    ops, host = _fit_and_predict()
    # a warm-up fit and requests before the window, whose spans and
    # device time must not count
    early_ops, early = _fit_and_predict(shift=-6_000)
    assert _read(metric, ops + early_ops, host + early) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_reads_nothing_without_the_program_spans(metric):
    ops, _ = _fit_and_predict()
    bench_only = [Event("bench.fit", 1_050, 9_000, "python"),
                  Event("bench.predict", 5_000, 5_500, "python")]
    assert _read(metric, ops, bench_only) is None


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_leaves_out_spans_outside_the_window(metric):
    ops, host = _fit_and_predict(shift=WINDOW[1])
    assert _read(metric, ops, host) is None


def test_busy_under_each_span_is_the_window_busy_time():
    ops, host = _fit_and_predict()
    ops.append(device_op("%copy.2 = f32[] copy()", 2_000, 2_400, "1"))
    t = Trace(ops, host)
    spans = [e for e in host if e.name.startswith("repro.round")]
    assert _spans.busy_each(t, spans) == [
        pytest.approx(t.busy_ns((e.start_ns, e.end_ns))) for e in spans]
    assert _spans.busy_each(Trace([], host), spans) == [0.0] * len(spans)
