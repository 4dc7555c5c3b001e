"""Scheduler packing: real residues answered / (rows x L) of the batches run."""
from benchmark.readers import batch_fill_pct as read  # noqa: F401
