"""Kernels / XLA ops: least time of a served batch's grouped products (the batches' own assignments,
or every expert's matrices read once a layer, `cca_flops`) over the `moe_experts` scope's device time."""
from benchmark import cca_readers


def read(obs):
    return cca_readers.moe_experts_roofline_pct(obs)
