"""End-to-end ``tokens_per_s``: see ``harness.derive.tokens_per_s``."""
from harness.derive import tokens_per_s as read  # noqa: F401
