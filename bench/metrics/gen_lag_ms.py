"""Per-layer metric ``gen_lag_ms``: see ``harness.derive.gen_lag_ms``."""
from harness.derive import gen_lag_ms as read  # noqa: F401
