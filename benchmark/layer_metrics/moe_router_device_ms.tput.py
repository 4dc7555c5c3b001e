"""Model (models/glm_moe.py): device ms a served batch under the `moe_router` scope:
the float32 product over all 512 experts, sigmoid, group-limited choice, weights, loads."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_router")
