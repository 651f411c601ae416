"""Per-layer metric ``queue_wait_p90_ms``: see ``harness.derive.queue_wait_p90_ms``."""
from harness.derive import queue_wait_p90_ms as read  # noqa: F401
