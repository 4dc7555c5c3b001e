"""Kernels / XLA ops: device ms a served batch under `cca_core` inside `cca`: the flash forward kernel with
grouped keys (`kernels/segment_flash.py`) and the head transposes around it."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "cca_core")
