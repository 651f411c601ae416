"""Per-layer metric ``device_idle_share``: see ``harness.derive.device_idle_share``."""
from harness.derive import device_idle_share as read  # noqa: F401
