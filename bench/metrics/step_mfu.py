"""Per-layer metric ``step_mfu``: see ``harness.derive.step_mfu``."""
from harness.derive import step_mfu as read  # noqa: F401
