"""Dispatcher: device time of the packed executable per batch (device trace)."""
from benchmark.readers import program_device_ms as read  # noqa: F401
