"""Model: device ms a step under the `local_track` scope, forward, backward and
recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "local_track")
