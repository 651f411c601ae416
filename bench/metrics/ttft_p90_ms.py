"""Per-layer metric ``ttft_p90_ms``: see ``harness.derive.ttft_p90_ms``."""
from harness.derive import ttft_p90_ms as read  # noqa: F401
