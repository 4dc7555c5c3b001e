"""Dispatcher: mean `serve.wait_slot` span (an assembled batch waiting for room
in the in-flight window) a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.span_mean_ms(obs, "serve.wait_slot")
