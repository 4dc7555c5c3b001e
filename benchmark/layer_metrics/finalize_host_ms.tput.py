"""Dispatcher: `serve.fan_out` + `serve.seal` seconds (host work after the
fetch) per batch sealed."""
from benchmark import span_readers


def read(obs):
    return span_readers.spans_per_batch_ms(
        obs, ("serve.fan_out", "serve.seal"), per="serve.seal")
