"""Work of one call of the paged tree-attention kernel, as a lower bound.

One call serves one attention layer of one verify step: every tree query
of a live row reads every cached K/V token of its row.  ``cached`` holds,
per live row, the tokens in that row's cache (``harness.derive`` gives
the engine's count, prompt + output - 1 when the step ran).  Counted:

    flops  4 * Hq * T * D per cached token (q.k and p.v); the tree's own
           T x T block is left out
    bytes  the cached K and V once, plus q, the tree K/V and the output of
           each live row, in the served dtype

The kernel's device time can only be longer than max(flops / peak,
bytes / bandwidth) of this work, so the share cannot pass 100%.
"""
from __future__ import annotations

# how the kernel's operation is named in the trace's ``XLA Ops`` line: the
# event's name is the HLO instruction, ``%tree_attention_template.N = ...``
OP_PATTERN = r"^%tree_attention_template(\.\d+)? "


def work(cached, T: int, n_q_heads: int, n_kv_heads: int, head_dim: int,
         dtype_bytes: int = 2):
    """(flops, bytes) of one call over rows with ``cached`` tokens each."""
    Hq, Hkv, D = n_q_heads, n_kv_heads, head_dim
    ctx = sum(int(c) for c in cached)
    flops = 4 * Hq * T * D * ctx
    rows = len(cached)
    bytes_ = dtype_bytes * (2 * Hkv * D * ctx
                            + rows * T * D * (2 * Hq + 2 * Hkv))
    return flops, bytes_
