"""Train step: device ms a step of the forward pass (`corrupt`, `jvp(forward)`,
`loss`), by named scope."""
from benchmark import span_readers


def read(obs):
    return span_readers.train_part_ms(obs, "fwd")
