"""Queue + scheduler: `serve.ingest` seconds (requests taken from the queue and
placed into open rows) summed, per batch launched."""
from benchmark import span_readers


def read(obs):
    return span_readers.spans_per_batch_ms(obs, ("serve.ingest",))
