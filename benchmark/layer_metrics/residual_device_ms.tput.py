"""Model (models/glm_moe.py): device ms a served batch under `residual`: what the CCA stack's float32
stream costs outside its two sublayers, a layer's two norms and its two scaled residual adds."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "residual")
