"""Model: train FLOPs over padded positions x steps / window / peak bf16 FLOP/s."""
from benchmark.readers import mfu_pct as read  # noqa: F401
