"""Summed device time of the fit's Pallas kernels per round of the
traced fit. The program's kernels today are ``fused_nested_round_pallas``,
``assign_top2_pallas`` and ``cluster_sum_pallas``; inside the jitted round
the trace names them ``pallas_call.<n>``, and called alone by their
wrapper's name. Both are custom calls with
``custom_call_target="tpu_custom_call"``, which is what is matched
(``bench.lib.trace.is_pallas``)."""
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "kernels"
UNIT = "ms"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "lower"

_work = load_module(Path(__file__).with_name("_fit_work.py"))


def read(obs):
    rounds = _work.fit_rounds(obs)
    ns = obs.trace.kernel_ns(obs.window)
    if not rounds or ns <= 0:
        return None
    return ns * 1e-6 / len(rounds)
