"""Device busy time per request in the traced predict window (the
``assign_top2_pallas`` kernel and the small ops around it), in ms."""
LAYER = "predict kernel"
UNIT = "ms"
MOVES = "predict_p50_ms"
SOURCE = "device_trace"
BETTER = "lower"


def read(obs):
    n = len(obs.trace.spans("bench.predict"))
    busy = obs.trace.busy_ns(obs.window)
    if n == 0 or busy <= 0:
        return None
    return busy * 1e-6 / n
