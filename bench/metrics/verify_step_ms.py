"""Per-layer metric ``verify_step_ms``: see ``harness.derive.verify_step_ms``."""
from harness.derive import verify_step_ms as read  # noqa: F401
