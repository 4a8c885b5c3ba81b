"""Backend-compile seconds before the window opened, from JAX's
``/jax/core/compile/backend_compile_duration`` monitoring events. With a
warm persistent cache this is what is left after the cache hits."""
LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
BETTER = "lower"


def read(obs):
    return obs.compile_setup["compile_s"]
