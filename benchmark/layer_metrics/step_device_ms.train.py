"""Train step: device-busy time of one train_step program (device trace)."""
from benchmark.readers import program_device_ms as read  # noqa: F401
