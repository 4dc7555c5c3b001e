"""Model (models/glm_moe.py): device ms a step under the `moe_experts` scope:
the grouped products over the held experts' blocks; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_experts")
