"""Device: backend compiles before the window that missed the persistent
cache; 0 in a run whose cache is warm."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.compiles(obs)
