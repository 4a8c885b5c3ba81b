"""Set-up time of a fit, in ms: the mean duration of the program's
``repro.fit.begin`` spans inside the traced window. That span covers
``engine.begin``: the host shuffle and gather of the rows, their copy to
the device, the dataset fingerprint, the first state and the kernel plan
(``repro.fit.shuffle``, ``.to_device`` and ``.init`` inside it)."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "fit set-up"
UNIT = "ms"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "lower"

_spans = load_module(Path(__file__).with_name("_spans.py"))


def read(obs):
    begins = _spans.spans_in(obs.trace, "repro.fit.begin", obs.window)
    if not begins:
        return None
    return sum(e.dur_ns for e in begins) * 1e-6 / len(begins)
