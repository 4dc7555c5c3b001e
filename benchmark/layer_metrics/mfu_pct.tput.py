"""Model: forward FLOPs of the window's batches, each at its row class, over
the whole window and the peak bf16 FLOP/s (saturated serving)."""
from benchmark.readers import serve_mfu_pct as read  # noqa: F401
