"""Model (models/glm_moe.py): device ms a served batch under the `mamba` scope: the Mamba-2 mixer whole
(the product in, the convolution, `ssd_core`, the gated norm, the product out), all layers of a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "mamba")
