"""Model (models/glm_moe.py): device ms a served batch under the `cca` scope: the CCA mixer whole
(projections, `cca_mix`, `cca_core`, the output product), all layers of a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "cca")
