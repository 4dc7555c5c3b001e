"""Trainer loop: mean device-idle gap between consecutive train_step programs (device trace)."""
from benchmark.readers import program_gap_ms as read  # noqa: F401
