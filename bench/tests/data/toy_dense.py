"""Model kind ``toy_dense``, for the test configuration ``toy-dense.json``
alone: a dense decoder (causal attention, SiLU-gated MLP), written apart
from ``bench/kinds/dense.py`` and found by the harness as any kind is.

The reference runs layer by layer in float32, eagerly; ``quant="fp8"``
rounds every matrix and the embedding through float8_e4m3 with one scale
per output channel (per embedding row).
"""
import jax
import jax.numpy as jnp
import numpy as np


def check_program(cfg, conf):
    m = conf["model"]
    for key, want in (("n_layers", m["n_layers"]), ("d_model", m["d_model"]),
                      ("n_heads", m["n_heads"]),
                      ("n_kv_heads", m["n_kv_heads"]),
                      ("resolved_head_dim", m["head_dim"]),
                      ("d_ff", m["d_ff"]), ("vocab_size", m["vocab_size"]),
                      ("dtype", conf["dtype"])):
        if getattr(cfg, key) != want:
            raise SystemExit(f"{cfg.name}.{key} is {getattr(cfg, key)!r}, "
                             f"the file says {want!r}")


def _fp8(w, axis):
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(w, quant):
    w = jnp.asarray(w, jnp.float32)
    return _fp8(w, -2) if quant == "fp8" else w


def _norm(h, g, eps):
    g = jnp.asarray(g, jnp.float32)
    return h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True) + eps) * (1 + g)


def _rope(x, theta):
    L, _, D = x.shape
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def logits_at(params, model, tokens, positions, quant=None, pad_to=0):
    m = model
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    L = max(len(tokens), pad_to)
    ids = np.zeros(L, np.int32)
    ids[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"], jnp.float32)[ids]
        if quant == "fp8":
            h = _fp8(h, -1)
        stack = params["groups"][0]
        mask = jnp.tril(jnp.ones((L, L), bool))
        for i in range(m["n_layers"]):
            lp = jax.tree.map(lambda x: x[i], stack)
            a, f = lp["attn"], lp["mlp"]
            x = _norm(h, lp["norm1"], m["rms_eps"])
            q = _rope((x @ _w(a["wq"], quant)).reshape(L, H, D),
                      m["rope_theta"])
            k = _rope((x @ _w(a["wk"], quant)).reshape(L, Hkv, D),
                      m["rope_theta"])
            v = (x @ _w(a["wv"], quant)).reshape(L, Hkv, D)
            k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
            s = jnp.where(mask, jnp.einsum("thd,shd->hts", q, k)
                          / np.sqrt(D), -jnp.inf)
            o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
            h = h + o.reshape(L, H * D) @ _w(a["wo"], quant)
            x = _norm(h, lp["norm2"], m["rms_eps"])
            h = h + (jax.nn.silu(x @ _w(f["w_gate"], quant))
                     * (x @ _w(f["w_up"], quant))) @ _w(f["w_down"], quant)
        rows = _norm(h, params["final_norm"], m["rms_eps"])[
            np.asarray(positions)]
        return rows @ _w(params["lm_head"], quant)


def flops_per_live_row(model, draft):
    """The base model's matrices over the tree's tokens (a lower bound:
    the draft heads are left out)."""
    m = model
    d, hd = m["d_model"], m["n_heads"] * m["head_dim"]
    layer = 2 * d * hd + 2 * d * m["n_kv_heads"] * m["head_dim"] + \
        3 * d * m["d_ff"]
    per_token = m["n_layers"] * layer + d * m["vocab_size"]
    return 2 * sum(draft["tree_nodes_per_depth"]) * per_token


def tree_work(cached, T_pad, model):
    H, Hkv, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    ctx = sum(cached)
    return (4 * H * T_pad * D * ctx,
            2 * (2 * Hkv * D * ctx + len(cached) * T_pad * D * (2 * H
                                                                + 2 * Hkv)))
