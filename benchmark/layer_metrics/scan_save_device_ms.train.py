"""Model, the scan over blocks: device ms a step of the operations directly in
the scan's `while` body outside every block scope (stacked saves and
slices)."""
from benchmark import span_readers


def read(obs):
    return span_readers.scan_save_ms(obs)
