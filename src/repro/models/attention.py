"""Attention blocks: GQA (optionally sliding-window, optionally encoder /
bidirectional) and DeepSeek-V2 MLA (multi-head latent attention).

Two execution modes:

* full-seq (train / prefill): blocked flash-style attention over the whole
  sequence; writes the KV cache when one is provided.
* verify  (decode / speculative): T new tokens (a candidate tree or chain)
  attend to the populated cache plus themselves under an ancestor mask.
  New KV entries are written at ``cache_len + arange(T)`` — the speculative
  scratch region; `commit` (serving/cache.py) compacts accepted entries.

A third sub-mode rides the full-seq math: **prefill continuation**
(``ai.prefill``, DESIGN.md §8 chunked prefill).  T chunk tokens at
absolute positions ``cache_len + arange(T)`` are persisted into the cache
exactly like a verify write, but attention then runs the SAME
``blocked_attention`` the whole-prompt prefill uses — over the cache view
with trailing positions masked by ``kv_valid_len`` — instead of the
verify path's plain-softmax ``masked_attention``.  Sharing the primitive
is what keeps chunked prefill byte-identical to the monolithic one: a
fully-masked trailing region is an exact no-op of the online softmax, so
the per-token math cannot depend on how the prompt was chunked.

The verify path speaks two cache layouts (DESIGN.md §6):

* dense: ``cache_k``/``cache_v`` are per-slot (B, S, ...) arrays in
  logical coordinates (``block_table`` None);
* paged: ``cache_k``/``cache_v`` are the global block pool — head-major
  ``(num_blocks, Hkv, block_size, D)`` for GQA, ``(num_blocks,
  block_size, r)`` for MLA's headless latents; the token axis is always
  second-to-last — and ``block_table`` (B, M) maps each
  slot's logical token-blocks to physical pool blocks.  New K/V scatter
  through the table at token granularity (O(B·T), no dense transient) and
  attention streams pool blocks natively through the attention-template
  instantiations (DESIGN.md §11): ``tree_attention_paged_bshd`` for
  full-attention GQA groups, ``tree_attention_paged_windowed_bshd`` for
  groups with sliding-window layers (the window rides as a traced
  scalar, so one kernel serves a group mixing local and global layers),
  and ``mla_attention_paged_bshd`` for MLA's absorbed latent math.
  Every group runs native; the per-layer table gather
  (``_paged_gather_layer``) survives only off the steady state — the
  chunked-prefill continuation (full-seq math over the cache view) and
  the ``paged_kernel=False`` test-oracle branch.

Param pytrees use a stacked leading layer axis when scanned.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention_template.ops import (
    mla_attention_paged_bshd, tree_attention_paged_windowed_bshd)
from repro.kernels.tree_attention.ops import tree_attention_paged_bshd
from repro.models.layers import (apply_rope, blocked_attention, dense_init,
                                 masked_attention, rope_sincos)


class AttnInputs(NamedTuple):
    """Everything the attention core needs besides x and params."""

    q_pos: jnp.ndarray                 # (B, T) absolute positions
    cache_k: Optional[jnp.ndarray]     # (B, S, Hkv, D), pool (N, Hkv, bs, D)
    cache_v: Optional[jnp.ndarray]     # when block_table is set, or None
    cache_len: Optional[jnp.ndarray]   # (B,) valid length
    tree_mask: Optional[jnp.ndarray]   # (T, T) ancestor-or-self bool
    window: jnp.ndarray | int          # 0 => full attention
    causal: bool
    block_table: Optional[jnp.ndarray] = None   # (B, M) int32 => pool layout
    paged_kernel: bool = True          # static: False forces the jnp gather
    #                                    fallback — TEST ORACLE only, no
    #                                    steady-state caller sets it
    windowed: bool = False             # static: group has sliding-window
    #                                    layers => windowed template variant
    #                                    (traced window + q_pos operands)
    prefill: bool = False              # static: cache + prefill => chunked
    #                                    prefill continuation (full-seq math)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq = cfg.n_heads_padded        # == n_heads unless pad_q_heads_to is set
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, hq * hd, dtype),
        "wk": dense_init(kk, d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(kv, d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ko, hq * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def lane_width(d: int) -> int:
    """``d`` rounded up to whole 128-lane tiles: the head width a KV cache
    stores when the kernel's page copy needs whole lane tiles."""
    return -(-d // 128) * 128


def gqa_fwd(p, cfg, x, ai: AttnInputs, *, scale: float | None = None,
            kv_width: int | None = None):
    """Returns (out (B,T,d), new_k (B,T,Hkv,D), new_v) — caller owns cache.

    ``scale``: the scores' scale (None: 1/sqrt(head_dim)).  ``kv_width``:
    the head width the cache stores, when wider than the head: q, k and v
    are zero-padded to it after RoPE (zero columns leave every score as it
    was; v's padded columns come out zero and are dropped), so every path
    below runs on lane-aligned heads.  Mosaic copies no page of a head
    width off the 128 lanes (``tests/test_tpu_compile.py``)."""
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.n_heads_padded, hd)
    k = k.reshape(B, T, cfg.n_kv_heads, hd)
    v = v.reshape(B, T, cfg.n_kv_heads, hd)

    sin, cos = rope_sincos(ai.q_pos, hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    if kv_width is not None and kv_width != hd:
        pad = ((0, 0), (0, 0), (0, 0), (0, kv_width - hd))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)

    if ai.cache_k is None:
        # full-sequence path (train / prefill): blocked flash attention
        kv_pos = ai.q_pos[0]  # assumes aligned positions across batch
        out = blocked_attention(q, k, v, ai.q_pos, kv_pos,
                                window=ai.window, causal=ai.causal,
                                scale=scale)
    elif ai.prefill:
        # chunked-prefill continuation: persist the chunk K/V at
        # [cache_len, cache_len+T), then run the SAME blocked attention
        # the whole-prompt prefill uses over the cache view — the masked
        # tail beyond cache_len+T is an exact online-softmax no-op, which
        # is what keeps chunked == unchunked byte-identical (§8)
        out, k, v = _prefill_continuation(q, k, v, ai, scale)
    elif ai.block_table is not None:
        # paged verify: scatter scratch through the table, stream the pool
        out, k, v = _paged_verify_gqa(q, k, v, ai, scale)
    else:
        # verify/decode path: write new kv into scratch region then attend
        S = ai.cache_k.shape[1]
        slot = ai.cache_len[:, None] + jnp.arange(T)[None, :]        # (B,T)
        bidx = jnp.arange(B)[:, None]
        ck = ai.cache_k.at[bidx, slot].set(k.astype(ai.cache_k.dtype))
        cv = ai.cache_v.at[bidx, slot].set(v.astype(ai.cache_v.dtype))
        mask = _verify_mask(ai, B, T, S)
        out = masked_attention(q, ck, cv, mask, scale)
        k, v = ck, cv  # return updated full cache
    out = out[..., :hd].reshape(B, T, cfg.n_heads_padded * hd)
    return out @ p["wo"], k, v


# ---------------------------------------------------------------------------
# chunked-prefill continuation (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _cache_write(cache_k, cache_v, k, v, ai: AttnInputs):
    """Persist T new per-token entries at logical [cache_len, cache_len+T)
    and return (updated_k, updated_v, k_view, v_view): the updated cache
    arrays in their own layout, plus the (B, S)-shaped logical view
    attention consumes.  Dense caches are their own view; pool-layout
    caches scatter through the block table and gather ONE layer's view
    (the per-layer transient, never the all-layer shim)."""
    B, T = k.shape[:2]
    if ai.block_table is not None:
        ck = _paged_scatter(cache_k, k, ai.cache_len, ai.block_table)
        cv = _paged_scatter(cache_v, v, ai.cache_len, ai.block_table)
        k_view, _ = _paged_gather_layer(ck, ai.block_table)
        v_view, _ = _paged_gather_layer(cv, ai.block_table)
        return ck, cv, k_view, v_view
    slot = ai.cache_len[:, None] + jnp.arange(T)[None, :]            # (B,T)
    bidx = jnp.arange(B)[:, None]
    ck = cache_k.at[bidx, slot].set(k.astype(cache_k.dtype))
    cv = cache_v.at[bidx, slot].set(v.astype(cache_v.dtype))
    return ck, cv, ck, cv


def _prefill_continuation(q, k, v, ai: AttnInputs, scale=None):
    """One chunk of a resumable prefill: write K/V, then full-seq blocked
    attention over the cache view.  Positions at or beyond
    ``cache_len + T`` (stale verify scratch, later chunks' zeros, NULL
    garbage) are masked via ``kv_valid_len``; right-pad inside the chunk
    needs no extra mask — pads sit after every real query, so causality
    already hides them."""
    T = q.shape[1]
    ck, cv, k_view, v_view = _cache_write(ai.cache_k, ai.cache_v, k, v, ai)
    S = k_view.shape[1]
    out = blocked_attention(q, k_view, v_view, ai.q_pos, jnp.arange(S),
                            window=ai.window, causal=ai.causal,
                            kv_valid_len=ai.cache_len + T, scale=scale)
    return out, ck, cv


# ---------------------------------------------------------------------------
# paged (block-pool) verify path
# ---------------------------------------------------------------------------


def _paged_scatter(pool, new, cache_len, block_table):
    """Write T per-token entries into the pool at the scratch region
    ``[cache_len, cache_len + T)``, mapped through the block table.
    pool: (N, [Hkv,] bs, D), token axis second-to-last; new: (B, T,
    [Hkv,] D) -> updated pool.  Positions past
    the table's reach clamp to the last logical slot (the engine
    guarantees coverage for live rows; dead rows' tables are all-NULL, so
    their writes land in the reserved garbage block)."""
    bs = pool.shape[-2]
    M = block_table.shape[1]
    T = new.shape[1]
    logical = cache_len[:, None] + jnp.arange(T)[None, :]            # (B,T)
    logical = jnp.minimum(logical, M * bs - 1)
    phys = jnp.take_along_axis(block_table, logical // bs, axis=1)   # (B,T)
    # (phys, ..., offset, :) indexes as (B, T, [Hkv,] D), matching ``new``
    return pool.at[phys, ..., logical % bs, :].set(new.astype(pool.dtype))


def _paged_gather_layer(pool, table):
    """One LAYER's logical view (B, M·bs, ...) plus the (B, M·bs) bool of
    positions backed by a real (non-NULL) block — the per-layer fallback's
    transient, and the only place the pool layout is re-flattened outside
    the shim (serving/paged.py) and the deliberately independent test /
    oracle copies."""
    bs = pool.shape[-2]
    B, M = table.shape
    view = jnp.moveaxis(pool[table], -2, 2)            # (B, M, bs, [Hkv,] D)
    view = view.reshape(B, M * bs, *view.shape[3:])
    covered = jnp.repeat(table != 0, bs, axis=1)
    return view, covered


def _paged_verify_gqa(q, k, v, ai: AttnInputs, scale=None):
    """Pool-layout verify for GQA: persist the T new K/V through the block
    table (token-granular scatter — the only writes of the step), then
    attend with the native paged template.  Groups with sliding-window
    layers (``ai.windowed``) take the windowed instantiation — the window
    is a traced per-layer scan operand, so the SAME compiled kernel
    serves a group mixing local and global layers (a <= 0 window is an
    exact mask no-op).  ``ai.paged_kernel=False`` is the test-oracle
    path: a per-layer table gather feeding the same masked attention the
    dense path uses; no steady-state caller sets it."""
    pool_k, pool_v, table = ai.cache_k, ai.cache_v, ai.block_table
    B, T = q.shape[:2]
    npk = _paged_scatter(pool_k, k, ai.cache_len, table)
    npv = _paged_scatter(pool_v, v, ai.cache_len, table)
    if ai.paged_kernel:
        tm = (ai.tree_mask if ai.tree_mask is not None
              else jnp.tril(jnp.ones((T, T), bool)))
        if ai.windowed:
            out = tree_attention_paged_windowed_bshd(
                q, npk, npv, k, v, tm, ai.cache_len, table, ai.q_pos,
                jnp.asarray(ai.window, jnp.int32))
        else:
            out = tree_attention_paged_bshd(q, npk, npv, k, v, tm,
                                            ai.cache_len, table, scale=scale)
    else:
        ck, covered = _paged_gather_layer(npk, table)
        cv, _ = _paged_gather_layer(npv, table)
        mask = _verify_mask(ai, B, T, ck.shape[1]) & covered[:, None, :]
        out = masked_attention(q, ck, cv, mask, scale)
    return out, npk, npv


def _verify_mask(ai: AttnInputs, B: int, T: int, S: int):
    """(B, T, S) mask: past-cache causal+window plus tree ancestor block."""
    kv_pos = jnp.arange(S)
    in_past = kv_pos[None, :] < ai.cache_len[:, None]                 # (B,S)
    j = kv_pos[None, :] - ai.cache_len[:, None]                       # (B,S)
    in_tree = (j >= 0) & (j < T)
    jc = jnp.clip(j, 0, T - 1)
    if ai.tree_mask is not None:
        tm = ai.tree_mask  # (T,T)
    else:  # chain: lower-triangular
        tm = jnp.tril(jnp.ones((T, T), bool))
    tree_bit = tm[:, jc]                                              # (T,B,S)
    tree_bit = jnp.transpose(tree_bit, (1, 0, 2))                     # (B,T,S)
    mask = (in_past[:, None, :] & ~in_tree[:, None, :]) | (
        in_tree[:, None, :] & tree_bit)
    w = jnp.asarray(ai.window)
    q_abs = ai.q_pos                                                  # (B,T)
    win_ok = jnp.where(w > 0,
                       q_abs[:, :, None] - kv_pos[None, None, :] < w,
                       True)
    return mask & win_ok


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank KV latent cache + decoupled RoPE key.
# Cache stores (c_kv: (B,S,r), k_rope: (B,S,rd)) instead of full K/V.
# Decode uses the absorbed formulation (score via latent, output via latent).
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], d, H * (m.qk_nope_dim + m.qk_rope_dim), dtype),
        "w_dkv": dense_init(ks[1], d, m.kv_lora_rank, dtype),
        "w_krope": dense_init(ks[2], d, m.qk_rope_dim, dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_dim, dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim, dtype),
        "wo": dense_init(ks[5], H * m.v_head_dim, d, dtype),
    }


def mla_fwd(p, cfg, x, ai: AttnInputs):
    """Returns (out, new_ckv (B,S|T,r), new_krope (B,S|T,rd))."""
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd, r = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim, m.kv_lora_rank

    q = (x @ p["w_dq"]).reshape(B, T, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    c_kv = x @ p["w_dkv"]                                   # (B,T,r)
    k_rope = x @ p["w_krope"]                               # (B,T,rd)

    sin, cos = rope_sincos(ai.q_pos, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0, :]

    scale = 1.0 / np.sqrt(nd + rd)

    if ai.cache_k is None:
        # train/prefill: expand latent to full K/V, blocked attention
        k_nope = (c_kv @ p["w_uk"]).reshape(B, T, H, nd)
        v = (c_kv @ p["w_uv"]).reshape(B, T, H, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, rd))],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad V up to qk dim for the shared kernel, then slice back
        kv_pos = ai.q_pos[0]
        out = blocked_attention(q_full, k, v, ai.q_pos, kv_pos,
                                window=ai.window, causal=ai.causal,
                                scale=scale)
        out = out.reshape(B, T, H * vd)
        return out @ p["wo"], c_kv, k_rope

    if ai.prefill:
        # chunked-prefill continuation: persist the chunk latents, expand
        # the WHOLE cached latent view to full K/V and run the same
        # blocked attention as the full-prefill path (not the absorbed
        # decode math) — chunking must not change which formulation
        # computed a prompt token's hidden state
        new_k, new_v, ckv_view, krope_view = _cache_write(
            ai.cache_k, ai.cache_v, c_kv, k_rope, ai)
        S = ckv_view.shape[1]
        k_nope = (ckv_view @ p["w_uk"]).reshape(B, S, H, nd)
        v_full = (ckv_view @ p["w_uv"]).reshape(B, S, H, vd)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_view[:, :, None, :],
                                      (B, S, H, rd))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = blocked_attention(q_full, k_full, v_full, ai.q_pos,
                                jnp.arange(S), window=ai.window,
                                causal=ai.causal, scale=scale,
                                kv_valid_len=ai.cache_len + T)
        out = out.reshape(B, T, H * vd)
        return out @ p["wo"], new_k, new_v

    # decode/verify: absorbed attention against the latent cache
    if ai.block_table is not None:
        # paged: scatter the T new latents through the table, then score
        # absorbed — q' = q_nope @ W_uk per head against the latent
        # stream directly.  The native MLA template instantiation
        # (DESIGN.md §11) streams the latent + rope pools as the K
        # concat and the latent as V, returning o_lat; only the
        # ``paged_kernel=False`` test oracle still gathers a dense view.
        table = ai.block_table
        new_k = _paged_scatter(ai.cache_k, c_kv, ai.cache_len, table)
        new_v = _paged_scatter(ai.cache_v, k_rope, ai.cache_len, table)
        if ai.paged_kernel:
            w_uk = p["w_uk"].reshape(r, H, nd)
            q_lat = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))     # (B,T,H,r)
            tm = (ai.tree_mask if ai.tree_mask is not None
                  else jnp.tril(jnp.ones((T, T), bool)))
            o_lat = mla_attention_paged_bshd(
                q_lat, q_rope.astype(jnp.float32), new_k, new_v, c_kv,
                k_rope, tm, ai.cache_len, table, scale=scale,
                q_pos=ai.q_pos if ai.windowed else None,
                window=(jnp.asarray(ai.window, jnp.int32)
                        if ai.windowed else None))
            w_uv = p["w_uv"].reshape(r, H, vd)
            out = jnp.einsum("bthr,rhv->bthv", o_lat,
                             w_uv.astype(jnp.float32))
            out = out.reshape(B, T, H * vd).astype(x.dtype)
            return out @ p["wo"], new_k, new_v
        ckv_all, covered = _paged_gather_layer(new_k, table)
        krope_all, _ = _paged_gather_layer(new_v, table)
        mask = _verify_mask(ai, B, T, ckv_all.shape[1]) & covered[:, None, :]
    else:
        S = ai.cache_k.shape[1]
        slot = ai.cache_len[:, None] + jnp.arange(T)[None, :]
        bidx = jnp.arange(B)[:, None]
        ckv_all = ai.cache_k.at[bidx, slot].set(c_kv.astype(ai.cache_k.dtype))
        krope_all = ai.cache_v.at[bidx, slot].set(
            k_rope.astype(ai.cache_v.dtype))
        new_k, new_v = ckv_all, krope_all
        mask = _verify_mask(ai, B, T, S)

    # absorbed: q' = q_nope @ W_uk^T per head -> score against latent directly
    w_uk = p["w_uk"].reshape(r, H, nd)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))            # (B,T,H,r)
    s = jnp.einsum("bthr,bsr->bths", q_lat,
                   ckv_all.astype(jnp.float32))
    s = s + jnp.einsum("bthr,bsr->bths", q_rope.astype(jnp.float32),
                       krope_all.astype(jnp.float32))
    s = s * scale
    s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
    pw = jax.nn.softmax(s, axis=-1)
    pw = jnp.where(jnp.isnan(pw), 0.0, pw)
    o_lat = jnp.einsum("bths,bsr->bthr", pw, ckv_all.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(r, H, vd)
    out = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv.astype(jnp.float32))
    out = out.reshape(B, T, H * vd).astype(x.dtype)
    return out @ p["wo"], new_k, new_v
