"""Host time of a round of the host loop, in ms: the mean, over the
program's ``repro.round`` spans inside the traced window, of the span's
duration less the device busy time under it. One span covers one
iteration of ``api.loop.run_loop``'s round loop: dispatch, the wait for
the device, the ``RoundInfo`` transfer, the bookkeeping and the schedule
update."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "host loop"
UNIT = "ms"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "lower"

_spans = load_module(Path(__file__).with_name("_spans.py"))


def read(obs):
    rounds = _spans.spans_in(obs.trace, "repro.round", obs.window)
    if not rounds:
        return None
    busy = _spans.busy_each(obs.trace, rounds)
    host = sum(e.dur_ns for e in rounds) - sum(busy)
    return host * 1e-6 / len(rounds)
