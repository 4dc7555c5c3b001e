"""Model (models/glm_moe.py): device ms a served batch under the `mla` scope: the latent
mixer's projections, rotary, flash core, head gate and output product."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "mla")
