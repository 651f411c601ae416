"""Per-layer metric ``tpot_p90_ms``: see ``harness.derive.tpot_p90_ms``."""
from harness.derive import tpot_p90_ms as read  # noqa: F401
