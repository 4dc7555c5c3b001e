"""Model (models/glm_moe.py): device ms a served batch under `cca_mix` inside `cca`: the two convolutions,
the means, the value shift, the norms and rotary: everything between the projections and the core."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "cca_mix")
