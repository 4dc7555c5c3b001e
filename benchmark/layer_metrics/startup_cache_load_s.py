"""Device: seconds of the loads from the persistent cache before the window
(`jax.compile` records with `cached=1`: retrieval and deserialization)."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.cache_load_s(obs)
