"""Device: 1 - union of device-op intervals over the traced window."""
from benchmark.readers import device_idle_pct as read  # noqa: F401
