"""Kernels / XLA ops: least time of the CCA attention cores of a batch (the pairs causal and inside a
document, or q, k, v and o moved once, `cca_flops`) over the `cca_core` scope's device time."""
from benchmark import cca_readers


def read(obs):
    return cca_readers.cca_core_roofline_pct(obs)
