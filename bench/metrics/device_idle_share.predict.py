"""Share of the traced predict window, in percent, in which no op ran on
the chip: 100 * (1 - device busy / window)."""
LAYER = "device"
UNIT = "%"
MOVES = "predict_p50_ms"
SOURCE = "device_trace"
BETTER = "lower"


def read(obs):
    lo, hi = obs.window
    busy = obs.trace.busy_ns(obs.window)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
