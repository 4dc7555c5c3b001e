"""Tracing's own check: share of `_packed_encode_batch`'s operation time whose
instruction the scope map names `encode` or `pool`."""
from benchmark import span_readers


def read(obs):
    return span_readers.packed_coverage_pct(obs)
