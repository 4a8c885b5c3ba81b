"""How late the load generator handed requests to the server: the 95th
percentile, over the traced window's requests, of (handed over - due),
in ms, on the host clock. A starved generator shows here, not as a fast
server."""
import numpy as np

LAYER = "load generator"
UNIT = "ms"
MOVES = "predict_p50_ms"
SOURCE = "host_clock"
BETTER = "lower"


def read(obs):
    log = obs.driver.log
    late = (log.sent - log.due)[np.isfinite(log.sent)] * 1e3
    if late.size == 0:
        return None
    v = np.sort(late)
    return float(v[max(0, int(np.ceil(0.95 * len(v))) - 1)])
