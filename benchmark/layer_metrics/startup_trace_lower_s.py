"""Model: seconds of tracing (outermost traces of 1 ms and more) and lowering
before the window: the Python a warm run still pays."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.trace_lower_s(obs)
