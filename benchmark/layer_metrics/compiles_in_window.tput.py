"""Device: `jax.compile` spans recorded inside the traced window (expected 0)."""
from benchmark import span_readers


def read(obs):
    return span_readers.compiles_in_window(obs)
