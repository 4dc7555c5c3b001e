"""Model (models/glm_moe.py): device ms a served batch under the `kda` scope: the KDA
mixers' projections, convolutions, gates, the recurrence (`kda_core`, inside it), head norm and output product."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "kda")
