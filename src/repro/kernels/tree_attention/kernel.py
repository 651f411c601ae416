"""Pallas TPU tree-verification attention — the Medusa/Hydra hot-spot.

One speculative step verifies T candidate-tree tokens against a KV cache
of length `cache_len` plus the tree tokens themselves under an ancestor
mask.  Since the attention-template refactor (DESIGN.md §11) both entry
points here are thin instantiations of ``kernels/attention_template``
(tree family); the windowed and MLA variants live in
``kernels/attention_template/ops.py``.

TPU-native design (vs the GPU approach of materializing a (T, S) additive
mask): the cache sweep is mask-free except for a per-block validity clamp
(k_pos < cache_len, via scalar prefetch), streamed HBM->VMEM in bk-sized
blocks with online softmax; the static (T, T) ancestor mask only touches
the final grid step.

Two cache layouts share the same sweep:

* ``tree_attention``      — dense per-slot cache ``(B, Hkv, S, D)``; the
  grid's cache axis walks S in ``bk``-sized strips.  ``bk=None`` takes
  the autotuned winner (key ``tree_dense|hd=<D>``); sizes that don't
  tile S are legalized by pad-or-clamp instead of asserting.
* ``tree_attention_paged`` — vLLM-style global block pool, head-major
  ``(num_blocks, Hkv, block_size, D)``, plus a per-slot block table
  ``(B, M)``; the grid's cache axis walks *table entries*, each index map
  scalar-prefetches ``block_table[b, j]`` so K/V blocks stream straight
  from the pool with no dense intermediate.  NULL-table entries (physical
  block 0) and entries past ``cache_len`` are compute-skipped, giving
  ragged early-exit for short slots; runs of skipped entries all map to
  block 0, so Mosaic's revisit elision drops their copies after the first.
  The cache tile here is the ALLOCATOR's ``block_size`` (sublane axis:
  must be a multiple of 8 — ValueError otherwise; bf16 pools want a
  multiple of 16, and larger blocks fill more of the MXU).

Grid: (B, Hq, n_cache_blocks + 1), innermost 'arbitrary' (sequential).
"""
from __future__ import annotations

from repro.kernels import tuned_block_sizes
from repro.kernels.attention_template.kernel import (  # noqa: F401
    NEG_INF, NULL_BLOCK, TemplateSpec, _init_scratch, _softmax_update,
    tree_attention_template)

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
                         cache_len, block_table, *,
                         interpret: bool | None = None):
    """Tree verification streaming K/V from a paged block pool.

    q: (B,Hq,T,D); pool_k/v: (num_blocks, Hkv, block_size, D) — the
    global head-major pool, NOT a per-slot view; tree_k/v: (B,Hkv,T,D);
    tree_mask: (T,T) bool ancestor-or-self; cache_len: (B,) int32
    committed length per slot; block_table: (B, M) int32 physical block ids (0 = NULL).
    interpret: None => auto (compile on TPU, interpret elsewhere).
    Returns (B,Hq,T,D).

    The grid's cache axis has one step per table entry: the index map
    scalar-prefetches ``block_table[b, j]``, so the per-step HBM traffic
    is exactly the blocks the slot owns below ``cache_len`` (plus the T
    tree tokens) — O(blocks touched), never O(B x max_len).  Positions
    inside the last committed block but >= cache_len are clamped in-body;
    NULL entries (holes or the unallocated tail) are compute-skipped and
    their contents can never reach the output.
    """
    return tree_attention_template(
        q, pool_k, pool_v, tree_k, tree_v, tree_mask, cache_len,
        block_table=block_table,
        spec=TemplateSpec(kind="tree", layout="paged"),
        interpret=interpret)
