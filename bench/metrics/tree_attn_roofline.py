"""Per-layer metric ``tree_attn_roofline``: see ``harness.derive.tree_attn_roofline``."""
from harness.derive import tree_attn_roofline as read  # noqa: F401
