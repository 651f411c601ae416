"""Per-layer metric ``preempt_share``: see ``harness.derive.preempt_share``."""
from harness.derive import preempt_share as read  # noqa: F401
