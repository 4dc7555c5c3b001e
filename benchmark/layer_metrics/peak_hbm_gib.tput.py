"""Device: memory_stats peak_bytes_in_use after the window."""
from benchmark.readers import peak_hbm_gib as read  # noqa: F401
