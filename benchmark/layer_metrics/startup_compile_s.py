"""Device: seconds of the backend compiles before the window that missed the
persistent cache (`jax.compile` records of the start-up collector, `cached=0`)."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.compile_s(obs)
