"""Model (models/glm_moe.py): device ms a step under the `lm_head` scope:
the final norm, the head's product and the chunked cross-entropy, main and prediction module; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "lm_head")
