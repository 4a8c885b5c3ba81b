"""The fit's Pallas kernels' share of their roofline, in percent: the
least time the chip needs for the traced fit's needed work
(``_fit_work.py``: operations over the bf16 peak, bytes over the HBM
bandwidth, the larger of the two per round) over the summed device time
of the Pallas kernels in the traced fit (``kernel_ms_per_round.py`` says
how they are found). Returns nothing where no such kernel ran."""
import sys
from pathlib import Path

from bench.lib.registry import load_module

LAYER = "kernels"
UNIT = "%"
MOVES = "fit_s"
SOURCE = "device_trace"
BETTER = "higher"

_work = load_module(Path(__file__).with_name("_fit_work.py"))


def read(obs):
    rounds = _work.fit_rounds(obs)
    ns = obs.trace.kernel_ns(obs.window)
    if obs.peaks is None or not rounds or ns <= 0:
        return None
    cfg = obs.cell.config
    least, compute = _work.least_time_s(rounds, int(cfg["k"]),
                                        int(cfg["d"]), obs.peaks)
    print(f"bench: fit_kernels_roofline: least time {least:.6f} s, "
          f"{compute:.1%} of it bound by operations, the rest by bytes; "
          f"kernel time {ns * 1e-9:.6f} s", file=sys.stderr)
    return 100.0 * least / (ns * 1e-9)
