"""The whole round's share of the chip's roofline, in percent: the least
time the chip needs for the traced fit's needed work (``_fit_work.py``)
over the device's busy time in the traced fit. It bounds the kernels'
share from below whatever runs the work, so work moved out of the
Pallas kernels still shows here."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "round"
UNIT = "%"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "higher"

_work = load_module(Path(__file__).with_name("_fit_work.py"))


def read(obs):
    rounds = _work.fit_rounds(obs)
    busy = obs.trace.busy_ns(obs.window)
    if obs.peaks is None or not rounds or busy <= 0:
        return None
    cfg = obs.cell.config
    least, _ = _work.least_time_s(rounds, int(cfg["k"]), int(cfg["d"]),
                                  obs.peaks)
    return 100.0 * least / (busy * 1e-9)
