"""Pallas TPU tree-verification attention — the Medusa/Hydra hot-spot.

One speculative step verifies T candidate-tree tokens against a KV cache
of length `cache_len` plus the tree tokens themselves under an ancestor
mask.  Since the attention-template refactor (DESIGN.md §11) both entry
points here are thin instantiations of ``kernels/attention_template``
(tree family); the windowed and MLA variants live in
``kernels/attention_template/ops.py``.

TPU-native design (vs the GPU approach of materializing a (T, S) additive
mask): the cache sweep is mask-free except for a per-block validity clamp
(k_pos < cache_len, via scalar prefetch), streamed HBM->VMEM in blocks
with online softmax; the static (T, T) ancestor mask only touches the
tree tokens, folded in last.

Two cache layouts share the online softmax:

* ``tree_attention``      — dense per-slot cache ``(B, Hkv, S, D)``; grid
  ``(B, Hq, S/bk + 1)``, the cache axis walking S in ``bk``-sized strips.
  ``bk=None`` takes the autotuned winner (key ``tree_dense|hd=<D>``);
  sizes that don't tile S are legalized by pad-or-clamp instead of
  asserting.
* ``tree_attention_paged`` — vLLM-style global block pool, head-major
  ``(num_blocks, Hkv, block_size, D)``, plus a per-slot block table
  ``(B, M)``; grid ``(B,)``, one program per row, in which the G query
  heads of each KV head form one ``(G*T, D)`` tile.  The body walks only
  the row's live pages, ``ceil(cache_len / block_size)`` of them, ``ppb``
  to a compute block: each page, all KV heads at once, is DMA'd from the
  pool in HBM into a double-buffered VMEM tile through
  ``block_table[b, p]``, the next block in flight while this one
  computes.  NULL entries (physical block 0) are
  never copied and are masked; pages past ``cache_len`` cost nothing.
  A page is the ALLOCATOR's ``block_size`` (sublane axis: must be a
  multiple of 8 — ValueError otherwise; bf16 pools want a multiple of
  16).  ``ppb`` and the tree axis' ``pad_to`` are resolved together,
  once, by ``tree_attention_paged_bshd``: the autotuned winner (key
  ``tree_paged|hd=<D>|bs=<block_size>``), else ``paged_defaults``, about
  128 tokens a block.
"""
from __future__ import annotations

from repro.kernels import tuned_block_sizes
from repro.kernels.attention_template.kernel import (
    TemplateSpec, paged_defaults, tree_attention_template)

_DENSE_DEFAULTS = {"bk": 512}


def tree_attention(q, cache_k, cache_v, tree_k, tree_v, tree_mask, cache_len,
                   *, bk: int | None = None, interpret: bool | None = None):
    """q: (B,Hq,T,D); cache_k/v: (B,Hkv,S,D); tree_k/v: (B,Hkv,T,D);
    tree_mask: (T,T) bool ancestor-or-self; cache_len: (B,) int32.
    bk: None => autotuned winner for this head dim (or 512).
    interpret: None => auto (compile on TPU, interpret elsewhere).
    Returns (B,Hq,T,D)."""
    if bk is None:
        bk = tuned_block_sizes("tree_dense", q.shape[-1],
                               defaults=_DENSE_DEFAULTS)["bk"]
    return tree_attention_template(
        q, cache_k, cache_v, tree_k, tree_v, tree_mask, cache_len,
        spec=TemplateSpec(kind="tree", layout="dense"), bk=bk,
        interpret=interpret)


def tree_attention_paged(q, pool_k, pool_v, tree_k, tree_v, tree_mask,
                         cache_len, block_table, *, ppb: int | None = None,
                         interpret: bool | None = None):
    """Tree verification streaming K/V from a paged block pool.

    q: (B,Hq,T,D); pool_k/v: (num_blocks, Hkv, block_size, D) — the
    global head-major pool, NOT a per-slot view; tree_k/v: (B,Hkv,T,D);
    tree_mask: (T,T) bool ancestor-or-self; cache_len: (B,) int32
    committed length per slot; block_table: (B, M) int32 physical block ids (0 = NULL).
    ppb: pages per compute block; None => the built-in default, about 128
    tokens (``paged_defaults``; ``tree_attention_paged_bshd`` resolves the
    autotuned winner).  ppb=1 updates page by page, as the per-entry grid
    did.
    interpret: None => auto (compile on TPU, interpret elsewhere).
    Returns (B,Hq,T,D).

    Each row's program copies exactly the pages the slot owns below
    ``cache_len`` (plus the T tree tokens) — O(blocks touched), never
    O(B x max_len), each page once for all its query heads.
    Positions inside the last committed page but >= cache_len are masked
    in-body; NULL entries (holes or the unallocated tail) are never
    copied and their contents can never reach the output.
    """
    if ppb is None:
        ppb = paged_defaults(pool_k.shape[2])["ppb"]
    return tree_attention_template(
        q, pool_k, pool_v, tree_k, tree_v, tree_mask, cache_len,
        block_table=block_table,
        spec=TemplateSpec(kind="tree", layout="paged"), ppb=ppb,
        interpret=interpret)
