"""Serving, recorded not judged: the window's whole quarter-seconds whose p95
reads over 1.5 x the median quarter-second's, summed in seconds."""
from benchmark.readers import stalled_seconds as read  # noqa: F401
