"""Train step: device ms a step in all-gather, reduce-scatter and all-reduce
operations of `train_step`, chip 0's plane."""
from benchmark import lm_readers


def read(obs):
    return lm_readers.collective_ms(obs)
