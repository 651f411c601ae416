"""Pure-jnp oracles for the template-only instantiations (windowed paged
verify, absorbed-MLA paged verify).  Deliberately written as the gathered
dense view + plain softmax — the very math the native kernels retired —
so the parity tests pin the kernels to an independent formulation."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tree_attention.ref import gather_pool_heads


def tree_attention_paged_windowed_ref(q, pool_k, pool_v, tree_k, tree_v,
                                      tree_mask, cache_len, block_table,
                                      q_pos, window):
    """Kernel-layout oracle.  q: (B,Hq,T,D); pool_k/v: (N,Hkv,bs,D);
    tree_k/v: (B,Hkv,T,D); q_pos: (B,T); window: int32 scalar (<=0 off).
    Tree token j sits at absolute position ``cache_len + j``."""
    B, Hq, T, D = q.shape
    Hkv, bs = pool_k.shape[1], pool_k.shape[2]
    M = block_table.shape[1]
    S = M * bs
    G = Hq // Hkv
    ck = gather_pool_heads(pool_k, block_table)            # (B,Hkv,S,D)
    cv = gather_pool_heads(pool_v, block_table)
    covered = jnp.repeat(block_table != 0, bs, axis=1)            # (B,S)

    kx = jnp.repeat(jnp.concatenate([ck, tree_k], axis=2), G, axis=1)
    vx = jnp.repeat(jnp.concatenate([cv, tree_v], axis=2), G, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) / (D ** 0.5)

    kv_pos = jnp.arange(S + T)
    in_cache = (kv_pos[None, :] < cache_len[:, None]) & (kv_pos[None] < S)
    in_cache = in_cache & jnp.pad(covered, ((0, 0), (0, T)))
    tm_full = jnp.zeros((T, S + T), bool).at[:, S:].set(tree_mask)
    mask = in_cache[:, None, :] | tm_full[None]                   # (B,T,S+T)

    # absolute kv positions: cache is its logical index; tree j is
    # cache_len + j
    abs_kv = jnp.where(kv_pos[None] < S, kv_pos[None],
                       cache_len[:, None] + (kv_pos[None] - S))   # (B,S+T)
    w = jnp.asarray(window)
    mask = mask & ((w <= 0) | (q_pos[:, :, None] - abs_kv[:, None, :] < w))

    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhts,bhsd->bhtd", p, vx.astype(jnp.float32)
                      ).astype(q.dtype)


def mla_attention_paged_ref(q_lat, q_rope, pool_lat, pool_rope, tree_lat,
                            tree_rope, tree_mask, cache_len, block_table, *,
                            scale, q_pos=None, window=None):
    """Model-layout oracle for the absorbed-MLA paged kernel: the
    per-layer gather + absorbed jnp math the kernel retired.  Returns
    o_lat (B, T, H, r)."""
    B, T, H, r = q_lat.shape
    bs = pool_lat.shape[1]
    M = block_table.shape[1]
    S = M * bs
    ckv = pool_lat[block_table].reshape(B, S, r)
    krope = pool_rope[block_table].reshape(B, S, -1)
    covered = jnp.repeat(block_table != 0, bs, axis=1)            # (B,S)

    ckv_all = jnp.concatenate([ckv, tree_lat.astype(ckv.dtype)], axis=1)
    krope_all = jnp.concatenate(
        [krope, tree_rope.astype(krope.dtype)], axis=1)

    s = jnp.einsum("bthr,bsr->bths", q_lat.astype(jnp.float32),
                   ckv_all.astype(jnp.float32))
    s = s + jnp.einsum("bthr,bsr->bths", q_rope.astype(jnp.float32),
                       krope_all.astype(jnp.float32))
    s = s * scale

    kv_pos = jnp.arange(S + T)
    in_cache = (kv_pos[None, :] < cache_len[:, None]) & (kv_pos[None] < S)
    in_cache = in_cache & jnp.pad(covered, ((0, 0), (0, T)))
    tm_full = jnp.zeros((T, S + T), bool).at[:, S:].set(tree_mask)
    mask = in_cache[:, None, :] | tm_full[None]                   # (B,T,S+T)
    if window is not None:
        abs_kv = jnp.where(kv_pos[None] < S, kv_pos[None],
                           cache_len[:, None] + (kv_pos[None] - S))
        w = jnp.asarray(window)
        mask = mask & ((w <= 0)
                       | (q_pos[:, :, None] - abs_kv[:, None, :] < w))

    s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bths,bsr->bthr", p, ckv_all.astype(jnp.float32)
                      ).astype(q_lat.dtype)
