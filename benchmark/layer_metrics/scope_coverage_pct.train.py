"""Tracing's own check: share of `train_step`'s operation time whose
instruction the scope map names with a scope of the program's."""
from benchmark import span_readers


def read(obs):
    return span_readers.train_coverage_pct(obs)
