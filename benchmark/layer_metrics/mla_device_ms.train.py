"""Model (models/glm_moe.py): device ms a step under the `mla` scope:
latent attention (projections, rotary, the causal segment core, the output product), the prediction module's with them; forward, backward and recomputation together."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "mla")
