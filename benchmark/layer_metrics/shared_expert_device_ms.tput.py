"""Model (models/glm_moe.py): device ms a served batch under the `shared_expert` scope."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "shared_expert")
