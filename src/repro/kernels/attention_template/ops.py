"""Model-layout wrappers for the template instantiations that did not
exist pre-refactor: native-paged sliding-window verify and native-paged
absorbed-MLA verify (DESIGN.md §11).

These are what ``models/attention.py`` calls on the serving hot path —
they retired the per-layer ``_paged_gather_layer`` fallback.  The legacy
entry points (``flash_attention_bshd``, ``tree_attention_bshd``,
``tree_attention_paged_bshd``) keep living in their own packages, now as
template instantiations themselves.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tuned_block_sizes
from repro.kernels.attention_template.kernel import (TemplateSpec,
                                                     paged_defaults,
                                                     tree_attention_template)


def _pad_axis1(t, Tp):
    if t.shape[1] == Tp:
        return t
    pad = [(0, 0)] * t.ndim
    pad[1] = (0, Tp - t.shape[1])
    return jnp.pad(t, pad)


def _pad_tree_mask(tree_mask, Tp):
    T = tree_mask.shape[0]
    if Tp == T:
        return tree_mask
    tm = jnp.zeros((Tp, Tp), bool).at[:T, :T].set(tree_mask)
    # padded query rows self-attend so their softmax is well-defined
    return tm.at[jnp.arange(T, Tp), jnp.arange(T, Tp)].set(True)


def tree_attention_paged_windowed_bshd(q, pool_k, pool_v, tree_k, tree_v,
                                       tree_mask, cache_len, block_table,
                                       q_pos, window, *,
                                       pad_to: int | None = None,
                                       ppb: int | None = None,
                                       interpret: bool | None = None):
    """Sliding-window tree verification streaming K/V from the block pool.

    Same contract as ``tree_attention_paged_bshd`` (pools head-major,
    ``(num_blocks, Hkv, block_size, D)``) plus ``q_pos`` (B, T)
    int32 absolute query positions and ``window`` (traced int32 scalar;
    <= 0 means full attention, so one compiled kernel serves a scan
    group mixing local and global layers).  Precondition: every real
    query row sits at ``q_pos >= cache_len`` (verify positions are
    ``cache_len + depth``).  pad_to/ppb: None => autotuned winners
    (``paged_defaults`` without one), looked up once and only for what
    is None.  Returns (B, T, Hq, D).
    """
    if pad_to is None or ppb is None:
        bs = pool_k.shape[2]
        tuned = tuned_block_sizes("tree_paged_windowed", q.shape[-1],
                                  block_size=bs, defaults=paged_defaults(bs))
        pad_to = tuned["pad_to"] if pad_to is None else pad_to
        ppb = tuned["ppb"] if ppb is None else ppb
    T = q.shape[1]
    Tp = -(-T // pad_to) * pad_to
    q, tree_k, tree_v, q_pos = (_pad_axis1(t, Tp)
                                for t in (q, tree_k, tree_v, q_pos))
    tm = _pad_tree_mask(tree_mask, Tp)
    tr = lambda t: t.transpose(0, 2, 1, 3)
    o = tree_attention_template(
        tr(q), pool_k, pool_v, tr(tree_k), tr(tree_v), tm, cache_len,
        block_table, window, q_pos,
        spec=TemplateSpec(kind="tree", layout="paged", windowed=True),
        ppb=ppb, interpret=interpret)
    return tr(o)[:, :T]


def mla_attention_paged_bshd(q_lat, q_rope, pool_lat, pool_rope, tree_lat,
                             tree_rope, tree_mask, cache_len, block_table, *,
                             scale: float, q_pos=None, window=None,
                             pad_to: int | None = None,
                             interpret: bool | None = None):
    """Absorbed-MLA tree verification streaming latents from the pools.

    K tiles are ``[latent ‖ rope]`` concatenated in-register; V is the
    latent stream, so the result is ``o_lat`` which the caller un-absorbs
    through ``w_uv``.  q_lat: (B,T,H,r) = q_nope @ w_uk (absorbed);
    q_rope: (B,T,H,rd); pool_lat: (N,bs,r); pool_rope: (N,bs,rd) — one
    latent head, so the kernel sees them as head-major (N,1,bs,*);
    tree_lat: (B,T,r); tree_rope: (B,T,rd).  ``scale`` is the absorbed
    score scale 1/sqrt(nd+rd) — NOT derivable from the latent ranks.
    Pass ``q_pos``/``window`` together to window the scores (unused by
    DeepSeek but the hook composes).  Returns o_lat (B, T, H, r).
    """
    B, T, H, r = q_lat.shape
    rd = q_rope.shape[-1]
    bs = pool_lat.shape[1]
    if pad_to is None:
        pad_to = tuned_block_sizes("mla_paged", r + rd, block_size=bs,
                                   defaults={"pad_to": 8})["pad_to"]
    windowed = window is not None
    if windowed and q_pos is None:
        raise ValueError("windowed MLA requires q_pos alongside window")
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)
    Tp = -(-T // pad_to) * pad_to
    q, tree_lat, tree_rope = (_pad_axis1(t, Tp)
                              for t in (q, tree_lat, tree_rope))
    tm = _pad_tree_mask(tree_mask, Tp)
    if windowed:
        q_pos = _pad_axis1(q_pos, Tp)
    tr = lambda t: t.transpose(0, 2, 1, 3)
    o = tree_attention_template(
        tr(q), pool_lat[:, None], None,
        tr(tree_lat[:, :, None, :]), None, tm, cache_len, block_table,
        window if windowed else None, q_pos if windowed else None,
        cache_k2=pool_rope[:, None],
        tree_k2=tr(tree_rope[:, :, None, :]),
        spec=TemplateSpec(kind="tree", layout="paged", mla=True,
                          windowed=windowed),
        scale=scale, interpret=interpret)
    return tr(o)[:, :T]                                      # (B,T,H,r)
