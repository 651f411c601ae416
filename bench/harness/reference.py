"""The plain reference of the ``dense`` kind (``kinds/dense.py``), in
float32 at ``highest`` matmul precision.

Written from the configuration file alone; nothing of the program is
imported.  They read the same weight arrays the program serves (drawn by
``weights.make_weights``), addressed by the names of the program's
parameter tree:

    embed (V, d), lm_head (d, V), final_norm (d,)
    groups[0]: the stack of layers with a leading layer axis:
               norm1, norm2, attn{wq, wk, wv, wo}, mlp{w_gate, w_up, w_down}

Equations, for one sequence of tokens x_0..x_{L-1}, all in float32:

    rms(h, g)   = h / sqrt(mean(h^2) + eps) * (1 + g)
    dense layer h += Attn(rms(h, norm1)); h += MLP(rms(h, norm2))
      Attn      causal GQA, query head j reads kv head j // (Hq / Hkv),
                scores q.k / sqrt(D), RoPE on q and k: the two halves
                (x1, x2) of each head turn by angle pos * theta^(-2i/D)
      MLP       (silu(x Wg) * (x Wu)) Wd
    logits      rms(h, final_norm) lm_head

``quant="fp8"`` is the control: every weight matrix (and the embedding)
passes through float8_e4m3 with one scale per output channel before use.
It is never run by the benchmark's own runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0                     # largest finite float8_e4m3fn


def _fp8(w, axis):
    """Round ``w`` through float8_e4m3 with a scale per slice along every
    axis except ``axis`` (the reduced one)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mat(w, quant):
    w = w.astype(F32)
    return _fp8(w, axis=-2) if quant == "fp8" else w


def _vec(w):
    return w.astype(F32)


def rms(h, g, eps):
    return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps) * (
        1.0 + _vec(g))


def _rope(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos.astype(F32)[:, None] * inv                 # (L, D/2)
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def dense_layer(lp, m, h, quant=None):
    L = h.shape[0]
    Hq, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    x = rms(h, lp["norm1"], m["rms_eps"])
    pos = jnp.arange(L)
    q = _rope((x @ _mat(a["wq"], quant)).reshape(L, Hq, D), pos,
              m["rope_theta"])
    k = _rope((x @ _mat(a["wk"], quant)).reshape(L, Hkv, D), pos,
              m["rope_theta"])
    v = (x @ _mat(a["wv"], quant)).reshape(L, Hkv, D)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(L, Hq * D) @ _mat(a["wo"], quant)
    x = rms(h, lp["norm2"], m["rms_eps"])
    f = lp["mlp"]
    g = jax.nn.silu(x @ _mat(f["w_gate"], quant)) * (x @ _mat(f["w_up"],
                                                              quant))
    return h + g @ _mat(f["w_down"], quant)


def _scan(fn, stack, m, h, quant):
    def body(h, lp):
        return fn(lp, m, h, quant), None
    return jax.lax.scan(body, h, stack)[0]


@functools.partial(jax.jit, static_argnames=("m_items", "quant"))
def _final_hidden(params, tokens, m_items, quant):
    m = dict(m_items)
    emb = params["embed"][tokens].astype(F32)
    if quant == "fp8":
        emb = _fp8(emb, axis=-1)
    h = _scan(dense_layer, params["groups"][0], m, emb, quant)
    return rms(h, params["final_norm"], m["rms_eps"])


@functools.partial(jax.jit, static_argnames=("vocab_block", "quant"))
def _logits(lm_head, rows, vocab_block, quant):
    d, V = lm_head.shape
    nb = V // vocab_block
    blocks = lm_head.reshape(d, nb, vocab_block).transpose(1, 0, 2)

    def one(w):
        return rows @ _mat(w, quant)
    out = jax.lax.map(one, blocks)                        # (nb, n, vb)
    return out.transpose(1, 0, 2).reshape(rows.shape[0], V)


def _vocab_block(V: int) -> int:
    for b in (32000, 16000, 8000, 4000, 2000, 1000, 500, 256, 128):
        if V % b == 0 and b <= V:
            return b
    return V


def logits_at(params, model: dict, tokens: np.ndarray, positions,
              quant=None, pad_to: int = 0) -> jnp.ndarray:
    """Reference logits (len(positions), V) of one sequence ``tokens`` at
    ``positions``.  The sequence is right-padded to ``pad_to`` so that
    every request reuses one compiled program; causality keeps the pad
    out of every real position."""
    L = max(len(tokens), pad_to)
    padded = np.zeros(L, np.int32)
    padded[:len(tokens)] = tokens
    items = tuple(sorted(model.items()))
    with jax.default_matmul_precision("highest"):
        h = _final_hidden(params, jnp.asarray(padded), items, quant)
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        return _logits(params["lm_head"], rows,
                       _vocab_block(model["vocab_size"]), quant)
