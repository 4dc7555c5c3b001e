"""Serving, recorded not judged: the median of the p95s of the window's whole
quarter-seconds, the tail between the process's stalls."""
from benchmark.readers import typical_p95_ms as read  # noqa: F401
