"""Queue + scheduler: median queue stage of RequestTrace.stages()."""
from benchmark.readers import queue_wait_ms as read  # noqa: F401
