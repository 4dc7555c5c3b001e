"""Kernels / XLA ops: roofline bound of one whole train_step over its device time."""
from benchmark.readers import program_roofline_pct as read  # noqa: F401
