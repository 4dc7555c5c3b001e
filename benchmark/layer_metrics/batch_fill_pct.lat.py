"""Scheduler packing: real residues answered / positions really computed
(row class x L of each batch run: `batched_positions`)."""
from benchmark.readers import batch_fill_pct as read  # noqa: F401
