"""Input pipeline: mean `train.data_wait` span (the trainer blocked in
next(batch_iterator)) a step."""
from benchmark import span_readers


def read(obs):
    return span_readers.span_mean_ms(obs, "train.data_wait")
