"""Kernels / XLA ops (ops/ssd.py): device ms a served batch under the `ssd_core` scope: the chunked
state-space recurrence alone, all Mamba layers of a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "ssd_core")
