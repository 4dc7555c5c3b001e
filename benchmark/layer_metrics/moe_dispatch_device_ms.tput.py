"""Model (models/glm_moe.py): device ms a served batch under the `moe_dispatch` scope:
the sort of the assignments by held expert, the block table, gathers and scatter-adds."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_dispatch")
