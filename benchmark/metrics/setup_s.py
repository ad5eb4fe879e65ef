"""setup_s (s): from the process's start to the first timed unit:
JAX start-up, the world, inputs and buffers, and the warm-up units
(compiles included, from the cache where it holds them)."""


def read(run):
    return run.setup_s
