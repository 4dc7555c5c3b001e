"""Model (models/glm_moe.py): device ms a step under the `moe_dispatch` scope:
the sort of the assignments by held expert, the block table, each block's gather and scatter-add; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_dispatch")
