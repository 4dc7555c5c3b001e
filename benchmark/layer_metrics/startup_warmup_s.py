"""Dispatcher: the `startup.warmup` spans summed over the row classes and
kinds `Server.start` warmed."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.warmup_s(obs)
