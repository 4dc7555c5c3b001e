"""Train step: device ms a step of the backward pass (under `transpose(jvp(`,
the recomputation with it), by named scope."""
from benchmark import span_readers


def read(obs):
    return span_readers.train_part_ms(obs, "bwd")
