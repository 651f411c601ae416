"""Per-layer metric ``prefill_chunk_ms``: see ``harness.derive.prefill_chunk_ms``."""
from harness.derive import prefill_chunk_ms as read  # noqa: F401
