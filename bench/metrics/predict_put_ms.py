"""Time a request spends putting its rows and the codebook on the
device, in ms: the mean duration of the program's ``repro.predict.put``
spans (both ``jnp.asarray`` calls of ``CodebookSnapshot.predict``)
inside the traced window."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "serve"
UNIT = "ms"
MOVES = "predict_p50_ms"
SOURCE = "device_trace"
BETTER = "lower"

_spans = load_module(Path(__file__).with_name("_spans.py"))


def read(obs):
    puts = _spans.spans_in(obs.trace, "repro.predict.put", obs.window)
    if not puts:
        return None
    return sum(e.dur_ns for e in puts) * 1e-6 / len(puts)
