"""Model: device ms a step under the `attention` scope, forward, backward and
recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "attention")
