"""Kernels / XLA ops: least time of the grouped products of a step (from the
assignments that really fell on the held experts) over the `moe_experts`
scope's device time."""
from benchmark import lm_readers


def read(obs):
    return lm_readers.moe_experts_roofline_pct(obs)
