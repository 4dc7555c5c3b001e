"""Serving, recorded not judged: the saturated cell's own p95 from the due time."""
from benchmark.readers import latency_p95_ms as read  # noqa: F401
