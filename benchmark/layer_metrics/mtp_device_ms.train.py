"""Model (models/glm_moe.py): device ms a step under the `mtp` scope:
the whole multi-token-prediction module (its join, its expert layer, its head); forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "mtp")
