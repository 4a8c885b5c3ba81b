"""Device busy time per round of the traced fit: the union of the
intervals in which any op ran on the chip (``XLA Ops`` line of the
trace) over the traced window, divided by the fit's rounds."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "round"
UNIT = "ms"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "lower"

_work = load_module(Path(__file__).with_name("_fit_work.py"))


def read(obs):
    rounds = _work.fit_rounds(obs)
    busy = obs.trace.busy_ns(obs.window)
    if not rounds or busy <= 0:
        return None
    return busy * 1e-6 / len(rounds)
