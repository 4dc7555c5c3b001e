"""Load generator: p95 of actual minus due send time."""
from benchmark.readers import generator_late_ms as read  # noqa: F401
