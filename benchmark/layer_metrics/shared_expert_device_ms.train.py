"""Model (models/glm_moe.py): device ms a step under the `shared_expert` scope:
the shared expert's SwiGLU over every token; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "shared_expert")
