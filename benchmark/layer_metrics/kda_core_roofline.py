"""Kernels / XLA ops: least time of the KDA cores of a batch (the token recurrence's operations
or its operands read once, `hybrid_flops`) over the `kda_core` scope's device time."""
from benchmark import hybrid_readers


def read(obs):
    return hybrid_readers.kda_core_roofline_pct(obs)
