"""Model (models/glm_moe.py): device ms a served batch under the `moe_experts` scope:
the grouped products over the blocks the batch's routing filled."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_experts")
