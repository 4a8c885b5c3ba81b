"""Host-loop rounds of the traced fit: the length of its ``Telemetry``
(one record per round of ``api.loop.run_loop``)."""
LAYER = "host loop"
UNIT = "rounds"
MOVES = "fit_s"
SOURCE = "program_counter"
BETTER = "lower"


def read(obs):
    fits = [r for r in obs.driver.records if not r.error]
    if not fits:
        return None
    return sum(len(r.telemetry) for r in fits) / len(fits)
