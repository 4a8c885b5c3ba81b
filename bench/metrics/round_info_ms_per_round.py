"""The ``RoundInfo`` transfer per round of the host loop, in ms: the
summed duration of the program's ``repro.round.info`` spans
(``api.loop.fetch_round_info``, the round's scalars to the host; once
more for each overflow retry) over the number of ``repro.round`` spans,
both inside the traced window."""
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
    infos = _spans.spans_in(obs.trace, "repro.round.info", obs.window)
    if not rounds or not infos:
        return None
    return sum(e.dur_ns for e in infos) * 1e-6 / len(rounds)
