"""Per-layer metric ``tokens_per_row_step``: see ``harness.derive.tokens_per_row_step``."""
from harness.derive import tokens_per_row_step as read  # noqa: F401
