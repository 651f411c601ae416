"""Pure-jnp oracle for tree_attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_attention_paged_ref(q, pool_k, pool_v, tree_k, tree_v, tree_mask,
                             cache_len, block_table):
    """Oracle for kernel.tree_attention_paged: assembles the dense logical
    view through the block table (the very shim the kernel kills), but
    masks NULL-table positions so the reserved block's contents can never
    leak into the output — matching the kernel's compute-skip exactly.

    q: (B,Hq,T,D); pool_k/v: (N, Hkv, bs, D); block_table: (B, M)."""
    bs = pool_k.shape[2]
    covered = jnp.repeat(block_table != 0, bs, axis=1)       # (B, M*bs)
    return tree_attention_ref(q, gather_pool_heads(pool_k, block_table),
                              gather_pool_heads(pool_v, block_table), tree_k,
                              tree_v, tree_mask, cache_len, kv_valid=covered)


def gather_pool_heads(pool, block_table):
    """Head-major pool (N, Hkv, bs, D) + table (B, M) -> the dense
    kernel-layout view (B, Hkv, M*bs, D)."""
    B, M = block_table.shape
    _, Hkv, bs, D = pool.shape
    return pool[block_table].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, M * bs, D)


def tree_attention_ref(q, cache_k, cache_v, tree_k, tree_v, tree_mask,
                       cache_len, kv_valid=None):
    """Same contract as kernel.tree_attention.  ``kv_valid``: optional
    (B, S) bool — cache positions additionally masked out when False
    (NULL-block holes in the paged layout)."""
    B, Hq, T, D = q.shape
    Hkv, S = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    kx = jnp.repeat(jnp.concatenate([cache_k, tree_k], axis=2), G, axis=1)
    vx = jnp.repeat(jnp.concatenate([cache_v, tree_v], axis=2), G, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) / (D ** 0.5)
    kv_pos = jnp.arange(S + T)
    in_cache = kv_pos[None, :] < cache_len[:, None]                 # (B, S+T)
    in_cache = in_cache & (kv_pos[None, :] < S)
    if kv_valid is not None:
        in_cache = in_cache & jnp.pad(kv_valid, ((0, 0), (0, T)))
    tm_full = jnp.zeros((T, S + T), bool).at[:, S:].set(tree_mask)
    mask = in_cache[:, None, None, :] | tm_full[None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhts,bhsd->bhtd", p, vx.astype(jnp.float32)
                      ).astype(q.dtype)
