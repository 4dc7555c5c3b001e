"""Model (models/glm_moe.py): device ms a step under the `moe_router` scope:
the router's float32 product, sigmoid, top-k and weights; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_router")
