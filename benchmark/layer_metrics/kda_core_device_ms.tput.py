"""Kernels / XLA ops: device ms a served batch under the `kda_core` scope: the chunked
delta-rule scan, its per-chunk preparation in XLA and the state walk (Pallas, `kernels/kda.py`)."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "kda_core")
