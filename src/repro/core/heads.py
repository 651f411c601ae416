"""Draft heads: Medusa (sequentially independent), Hydra (sequentially
dependent, paper §3) and the Hydra++ recipe (§3.1: deeper MLPs, teacher
distillation — see core/distill.py — and PrefixAttention).

Head i (0-based) predicts the token (i+1) steps ahead of the last verified
token x_t:

  Medusa:  p(x_{t+1+i}) = f_i(h)                      h = base hidden of the
                                                      token BEFORE x_t
  Hydra:   p(x_{t+1+i}) = f_i(h, E[x_t], E[x̂_{t+1}], ..., E[x̂_{t+i}])

Hydra head MLP: Linear((i+2)·d -> d) + SiLU, then (n_mlp_layers-1) residual
SiLU blocks, then the unembedding (tied to the base lm_head by default —
Medusa-style per-head unembeddings are supported via tie_unembed=False).

PrefixAttention (Hydra++): one extra trainable decoder layer on top of the
frozen base model's hidden-state stream, queried once per decoding step; all
heads read its output instead of the raw base hidden state.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.attention import AttnInputs, gqa_fwd, init_gqa
from repro.models.layers import dense_init, init_mlp, mlp_fwd, rms_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames="cfg")
def init_draft_params(key, cfg: ModelConfig):
    """Random draft-head parameters in ``cfg.dtype``, built under jit
    like ``models.model.init_params``."""
    dc = cfg.draft
    d, V = cfg.d_model, cfg.vocab_size
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, dc.n_heads + 2)
    heads = []
    for i in range(dc.n_heads):
        hk = jax.random.split(keys[i], dc.n_mlp_layers + 1)
        in_dim = d if dc.kind == "medusa" else (i + 2) * d
        hp = {"w_in": dense_init(hk[0], in_dim, d, dtype),
              # trainable norm before the (frozen, tied) unembedding: the
              # head must be able to match the base model's final-norm
              # hidden-state scale or its logits stay near-uniform
              "out_norm": jnp.zeros((d,), dtype)}
        for m in range(dc.n_mlp_layers - 1):
            hp[f"w_res{m}"] = dense_init(hk[1 + m], d, d, dtype,
                                         scale=0.02)  # near-identity start
        if not dc.tie_unembed:
            hp["unembed"] = dense_init(hk[-1], d, V, dtype)
        heads.append(hp)
    params = {"heads": heads}
    if dc.prefix_attention:
        pk1, pk2 = jax.random.split(keys[-1])
        params["prefix"] = {
            "norm1": jnp.zeros((d,), dtype),
            "norm2": jnp.zeros((d,), dtype),
            "attn": init_gqa(pk1, cfg, dtype),
            "mlp": init_mlp(pk2, d, cfg.d_ff, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# prefix attention
# ---------------------------------------------------------------------------


def prefix_forward(dp, cfg: ModelConfig, hidden, positions, *,
                   cache_k=None, cache_v=None, cache_len=None,
                   tree_mask=None, block_table=None, prefill=False):
    """Extra decoder layer over the base model's hidden-state stream.

    hidden: (B, T, d). Full-seq (cache_* None) for training; cache path for
    decoding (chain mask by default).  ``block_table`` switches cache_k/v
    to the paged pool layout (same per-slot tables as the KV caches).
    ``prefill=True`` (with a cache) runs the chunked-prefill continuation
    instead of the decode path: the T hiddens are one prompt chunk at
    ``cache_len + arange(T)``, attended with the full-seq blocked math
    (DESIGN.md §8).  Returns (out, new_k, new_v)."""
    p = dp["prefix"]
    ai = AttnInputs(q_pos=positions, cache_k=cache_k, cache_v=cache_v,
                    cache_len=cache_len, tree_mask=tree_mask,
                    window=jnp.int32(0), causal=True,
                    block_table=block_table, prefill=prefill)
    a, nk, nv = gqa_fwd(p["attn"], cfg, rms_norm(hidden, p["norm1"],
                                                 cfg.rms_eps), ai)
    h = hidden + a
    h = h + mlp_fwd(p["mlp"], rms_norm(h, p["norm2"], cfg.rms_eps))
    return h, nk, nv


def init_prefix_cache(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


# ---------------------------------------------------------------------------
# head application
# ---------------------------------------------------------------------------


def head_logits(dp, cfg: ModelConfig, base_params, i: int, h, path_embs):
    """Head i logits.

    h: (..., d) draft-model hidden state (base hidden or prefix output).
    path_embs: (..., i+1, d) embeddings [E(x_t), E(x̂_{t+1}),...,E(x̂_{t+i})]
    (ignored for Medusa heads). Returns fp32 logits (..., V)."""
    hp = dp["heads"][i]
    if cfg.draft.kind == "medusa":
        x = h
    else:
        flat = path_embs.reshape(*path_embs.shape[:-2], -1)
        x = jnp.concatenate([h, flat.astype(h.dtype)], axis=-1)
    z = jax.nn.silu(x @ hp["w_in"])
    for m in range(cfg.draft.n_mlp_layers - 1):
        z = z + jax.nn.silu(z @ hp[f"w_res{m}"])
    z = rms_norm(z, hp["out_norm"])
    if cfg.draft.tie_unembed:
        # the base model is FROZEN (paper §5): the tied unembedding must
        # not receive gradients from head training
        unembed = jax.lax.stop_gradient(
            base_params["embed"].T if cfg.tie_embeddings
            else base_params["lm_head"])
    else:
        unembed = hp["unembed"]
    return z.astype(jnp.float32) @ unembed.astype(jnp.float32)


# ---------------------------------------------------------------------------
# tree drafting
# ---------------------------------------------------------------------------


def _total_order_key(x):
    """float32 -> int32 whose signed order is IEEE-754 totalOrder, the order
    ``lax.top_k`` ranks by (-0.0 below +0.0, NaNs at the ends); an
    involution, so it also maps a key back to the float's bits."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ np.int32(0x7FFFFFFF), b)


def _argmax_key(key, idx):
    """Largest key over the last axis, the lowest index among equals: one
    reduction of (key, index) pairs.  Returns (key, index), each (...,)."""
    def better(a, b):
        take_a = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return (jnp.where(take_a, a[0], b[0]), jnp.where(take_a, a[1], b[1]))
    init = (jnp.int32(np.iinfo(np.int32).min), jnp.int32(key.shape[-1]))
    return jax.lax.reduce((key, idx), init, better, (key.ndim - 1,))


def top_k_at_ranks(x, ranks):
    """``lax.top_k(x, k)`` over the last axis, read at one rank per
    position of the axis before it, without sorting.

    x: (..., n, V) float32; ranks: (n,) static ints, k = max(ranks) + 1.
    Returns (values, indices), each (..., n): exactly what ``top_k`` puts at
    those ranks, ties ranked by lower index.  Runs k rounds of max-and-mask,
    each one pass over x: a round takes the largest remaining entry and
    retires its index; retired entries lose every comparison, so even
    -inf or NaN entries are ranked as ``top_k`` ranks them."""
    V = x.shape[-1]
    key = _total_order_key(x)
    iota = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    retired = jnp.zeros(key.shape, jnp.bool_)
    keys, idxs = [], []                   # round j: rank j of every position
    for _ in range(int(np.max(ranks)) + 1):
        k, i = _argmax_key(jnp.where(retired, np.iinfo(np.int32).min, key),
                           jnp.where(retired, V, iota))
        keys.append(k)
        idxs.append(i)
        retired = retired | (iota == i[..., None])
    k = jnp.stack([keys[r][..., j] for j, r in enumerate(ranks)], -1)
    i = jnp.stack([idxs[r][..., j] for j, r in enumerate(ranks)], -1)
    return jax.lax.bitcast_convert_type(_total_order_key(k), jnp.float32), i


def draft_tree_tokens(dp, cfg: ModelConfig, base_params, tree, h, last_tok):
    """Populate the candidate tree (paper §2 'tree decoding' + §3).

    h: (B, d); last_tok: (B,). Returns (tokens (B,T) int32, logp (B,T) fp32
    draft log-prob of each node's token given its path).
    Level-by-level: depth-d nodes are filled from head d-1 queried with the
    (sequentially dependent, for Hydra) path embeddings.
    """
    B = h.shape[0]
    T = tree.size
    dep = tree.depth
    anc = tree.ancestors                  # (T, D+1) numpy
    rank = tree.child_rank
    embed = base_params["embed"]

    tokens = jnp.zeros((B, T), jnp.int32).at[:, 0].set(last_tok)
    logp = jnp.zeros((B, T), jnp.float32)

    for d in range(1, tree.max_depth + 1):
        nodes = np.where(dep == d)[0]
        if len(nodes) == 0:
            break
        head_i = d - 1
        # path node ids (static): ancestors at depths 0..d-1
        path_ids = anc[nodes][:, :d]                      # (n, d)
        path_toks = tokens[:, path_ids]                   # (B, n, d)
        path_embs = embed[path_toks]                      # (B, n, d, dm)
        if cfg.draft.kind == "medusa":
            hh = jnp.broadcast_to(h[:, None, :], (B, len(nodes), h.shape[-1]))
            lg = head_logits(dp, cfg, base_params, head_i, hh, None)
        else:
            hh = jnp.broadcast_to(h[:, None, :], (B, len(nodes), h.shape[-1]))
            lg = head_logits(dp, cfg, base_params, head_i, hh, path_embs)
        lp = jax.nn.log_softmax(lg, axis=-1)              # (B, n, V)
        sel_lp, sel_tok = top_k_at_ranks(lp, rank[nodes])  # (B, n)
        tokens = tokens.at[:, jnp.asarray(nodes)].set(sel_tok)
        logp = logp.at[:, jnp.asarray(nodes)].set(sel_lp)
    return tokens, logp
