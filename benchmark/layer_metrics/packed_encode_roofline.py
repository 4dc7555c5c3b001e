"""Kernels / XLA ops: roofline bound of every packed embed batch of the traced
window at its OWN row class, summed, over the device time of the same runs."""
from benchmark.readers import packed_roofline_pct as read  # noqa: F401
