"""Backend compiles inside the traced fit, from JAX's monitoring events.
Every (b, capacity) bucket was compiled or loaded at set-up, so this
should read 0."""
LAYER = "host loop"
UNIT = "count"
MOVES = "fit_s"
SOURCE = "program_counter"
BETTER = "lower"


def read(obs):
    return obs.compile_window["compiles"]
