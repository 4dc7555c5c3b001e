"""Model (models/glm_moe.py): device ms a served batch under the `gqa` scope: the attention layer whole
(q, k, v, the flash core over grouped keys, the output product)."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "gqa")
