"""Time a request spends waiting for the device and bringing its labels
to the host, in ms: the mean duration of the program's
``repro.predict.fetch`` spans (``np.asarray`` of the labels in
``CodebookSnapshot.predict``) inside the traced window."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "serve"
UNIT = "ms"
MOVES = "predict_p50_ms"
SOURCE = "device_trace"
BETTER = "lower"

_spans = load_module(Path(__file__).with_name("_spans.py"))


def read(obs):
    fetches = _spans.spans_in(obs.trace, "repro.predict.fetch", obs.window)
    if not fetches:
        return None
    return sum(e.dur_ns for e in fetches) * 1e-6 / len(fetches)
