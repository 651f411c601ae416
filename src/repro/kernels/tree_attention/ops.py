"""Jit'd wrappers: model layout + T padding to MXU-friendly multiples.

``tree_attention_bshd`` takes the dense per-slot cache; ``tree_attention_
paged_bshd`` takes the global block pool + per-slot block tables and is
what the paged serving engine's verify path calls (models/attention.py)
for full-attention groups — windowed and MLA groups go through the
sibling instantiations in ``kernels/attention_template/ops.py``.

``pad_to=None`` consults the autotuner winner cache (the tree-family
"query block" is the padded T, so the tuner owns it like any other block
size); pass an explicit multiple to pin it.  The paged wrapper resolves
``pad_to`` and the pages per compute block ``ppb`` in one lookup, only
for what the caller left None: the sweep harness passes both, so timing
a candidate never re-enters the lookup.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tuned_block_sizes
from repro.kernels.attention_template.kernel import paged_defaults
from repro.kernels.tree_attention.kernel import (tree_attention,
                                                 tree_attention_paged)

_PAD_DEFAULTS = {"pad_to": 8}


def _pad_tree(q, tree_k, tree_v, tree_mask, pad_to: int):
    """Pad the tree axis T up to a multiple of pad_to; padded query rows
    self-attend (diag True) so their softmax is well-defined."""
    T = q.shape[1]
    Tp = -(-T // pad_to) * pad_to
    if Tp == T:
        return q, tree_k, tree_v, tree_mask, T
    padT = lambda t: jnp.pad(t, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    tm = jnp.zeros((Tp, Tp), bool).at[:T, :T].set(tree_mask)
    tm = tm.at[jnp.arange(T, Tp), jnp.arange(T, Tp)].set(True)
    return padT(q), padT(tree_k), padT(tree_v), tm, T


def tree_attention_bshd(q, cache_k, cache_v, tree_k, tree_v, tree_mask,
                        cache_len, *, pad_to: int | None = None,
                        bk: int | None = None,
                        interpret: bool | None = None):
    """q: (B,T,Hq,D); cache/tree k,v: (B,S|T,Hkv,D); tree_mask (T,T).
    pad_to/bk: None => autotuned winners (the sweep harness passes both
    explicitly so candidate timing never re-enters the lookup).
    interpret: None => auto (compile on TPU, interpret elsewhere)."""
    if pad_to is None:
        pad_to = tuned_block_sizes("tree_dense", q.shape[-1],
                                   defaults=_PAD_DEFAULTS)["pad_to"]
    q, tree_k, tree_v, tree_mask, T = _pad_tree(q, tree_k, tree_v,
                                                tree_mask, pad_to)
    o = tree_attention(q.transpose(0, 2, 1, 3),
                       cache_k.transpose(0, 2, 1, 3),
                       cache_v.transpose(0, 2, 1, 3),
                       tree_k.transpose(0, 2, 1, 3),
                       tree_v.transpose(0, 2, 1, 3),
                       tree_mask, cache_len, bk=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3)[:, :T]


def tree_attention_paged_bshd(q, pool_k, pool_v, tree_k, tree_v, tree_mask,
                              cache_len, block_table, *,
                              pad_to: int | None = None,
                              ppb: int | None = None,
                              interpret: bool | None = None):
    """q/tree k,v: (B,T,H*,D) model layout; pool_k/v: the head-major
    global pool (num_blocks, Hkv, block_size, D) — streamed in place,
    never gathered;
    block_table: (B, M) int32.  pad_to/ppb: None => autotuned winners
    (``paged_defaults`` without one).  Returns (B,T,Hq,D)."""
    if pad_to is None or ppb is None:
        bs = pool_k.shape[2]
        tuned = tuned_block_sizes("tree_paged", q.shape[-1], block_size=bs,
                                  defaults=paged_defaults(bs))
        pad_to = tuned["pad_to"] if pad_to is None else pad_to
        ppb = tuned["ppb"] if ppb is None else ppb
    q, tree_k, tree_v, tree_mask, T = _pad_tree(q, tree_k, tree_v,
                                                tree_mask, pad_to)
    o = tree_attention_paged(q.transpose(0, 2, 1, 3), pool_k, pool_v,
                             tree_k.transpose(0, 2, 1, 3),
                             tree_v.transpose(0, 2, 1, 3),
                             tree_mask, cache_len, block_table, ppb=ppb,
                             interpret=interpret)
    return o.transpose(0, 2, 1, 3)[:, :T]
