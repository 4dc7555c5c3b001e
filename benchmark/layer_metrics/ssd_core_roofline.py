"""Kernels / XLA ops: least time of the state-space recurrences of a batch (the token recurrence's
operations or its operands moved once, `nemotron_flops`) over the `ssd_core` scope's device time."""
from benchmark import nemotron_readers


def read(obs):
    return nemotron_readers.ssd_core_roofline_pct(obs)
