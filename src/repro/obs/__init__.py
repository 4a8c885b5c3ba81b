"""repro.obs — structured tracing and unified metrics.

One observability plane for the whole stack:

  * `SpanTracer` / `read_events` / `summarize` (``trace.py``) —
    rotating JSONL span+event logs, per-process on multihost, with a
    merge reader and a ``python -m repro.obs`` CLI;
  * `MetricsRegistry` / `Counter` / `Gauge` / `Histogram`
    (``metrics.py``) — the registry generalized out of serve/metrics,
    with JSON and Prometheus-text exporters (`ServeMetrics` lives here
    now; ``repro.serve.metrics`` re-exports it);
  * `FitObserver` (``sink.py``) — the concrete sink behind
    ``FitConfig(trace_dir=...)`` that the host loop's `ObsSink` seam
    writes through.

The package imports NO jax and NO numpy: attaching it to the host loop
cannot provoke a device sync (the hostsync auditor verifies this on
every backend), and the reader CLI runs anywhere Python does.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, LatencyHistogram,
                               MetricsRegistry, ServeMetrics)
from repro.obs.sink import FitObserver
from repro.obs.trace import (OBS_SCHEMA, SpanTracer, read_events, summarize,
                             tail_events, trace_files)

__all__ = [
    "OBS_SCHEMA", "SpanTracer", "read_events", "summarize", "tail_events",
    "trace_files",
    "Counter", "Gauge", "Histogram", "LatencyHistogram", "MetricsRegistry",
    "ServeMetrics",
    "FitObserver",
]
