"""Kernels and XLA ops: device ms a step under the `mla_core` scope: the
attention core inside latent attention (the flash kernels, forward,
recomputation and both backward kernels, with the head transposes and the
per-query broadcasts around them), the prediction module's with them. A
program without the scope (before PR 31) reads as nothing."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "mla_core") or None
