"""Host time per request in ``CodebookSnapshot.predict``: the mean over
the traced window's requests of (the benchmark's ``bench.predict`` span
around the call - the device busy time inside it). Span and device ops
are on the profiler's one clock."""
LAYER = "serve"
UNIT = "ms"
MOVES = "predict_p50_ms"
SOURCE = "device_trace"
BETTER = "lower"


def read(obs):
    spans = obs.trace.spans("bench.predict")
    if not spans:
        return None
    host = [s.dur_ns - obs.trace.busy_ns((s.start_ns, s.end_ns))
            for s in spans]
    return sum(host) * 1e-6 / len(host)
