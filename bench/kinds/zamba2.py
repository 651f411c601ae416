"""Model kind ``zamba2``: a Mamba2 backbone with shared attention blocks,
as a configuration file's ``model`` states it
(``bench/configs/zamba2-7b.json``; ``kinds/dense.py`` lists the names the
harness reads).  The reference is written from the published equations
alone and imports nothing of the program (``check_program`` alone reads
the program's configuration).  It reads the weight arrays the program serves
(drawn by ``harness.weights``), addressed by the names of the program's
parameter tree:

    embed (V, d), final_norm (d,); the output head lm_head (d, V), or
               embed^T where the model ties them
    groups[i]  in layer order: a run of plain Mamba2 layers stacked on a
               leading axis {norm, mamba}, or one hybrid layer
               {linear, lora_a, lora_b, layers: {norm, mamba} (a stack
               of one)}
    shared[b]  {norm_in, attn: {wq, wk, wv, wo}, norm_ff,
                mlp: {w_gate, w_up, w_down}}
    mamba      {w_in, conv_w, conv_b, a_log, d_skip, dt_bias, norm, w_out}

Equations, for one sequence, all in float32 (rms(h, g) = h / sqrt(mean
h^2 + eps) * (1 + g); GroupRMS takes it over each of G equal slices):

    e = embed[x]; h = e
    layer l, if the j-th of hybrid_layer_ids (block b = j mod num_mem_blocks):
      u = rms([h ‖ e], norm_in_b)                                (2d wide)
      q, k, v = u W{q,k,v}_b: H heads of D; RoPE over all D; scores
                q.k * attn_scale, causal; a = Attn W o_b            (d)
      m = rms(a, norm_ff_b); [g ‖ up] = m [Wg ‖ Wu]_b + (m A_j) B_j
      t = ((act(g) * up) Wdown_b) Lin_j;  x = h + t
    else x = h
    h = h + Mamba2_l(rms(x, norm_l))
    Mamba2(x): [z ‖ xBC ‖ dt] = x W_in; xBC = silu(causal depthwise conv +
      bias); x_h, B_g, C_g from xBC (head h reads group h // (H / G));
      dt = softplus(dt + dt_bias); A = -exp(a_log);
      S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T, y_t = C_t S_t + D x_t,
      a token at a time; out = GroupRMS(y * silu(z), norm) W_out
    logits = rms(h, final_norm) lm_head

``quant="fp8"`` is the control: every weight matrix and the embedding pass
through float8_e4m3 with one scale per output channel before use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from work import tree_attn

F32 = jnp.float32
FP8_MAX = 448.0


def _fp8(w, axis):
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mat(w, quant):
    w = jnp.asarray(w, F32)
    return _fp8(w, axis=-2) if quant == "fp8" else w


def _rms(h, g, eps):
    return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps) * (
        1.0 + jnp.asarray(g, F32))


def _rope(x, theta):
    L, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(L, dtype=F32)[:, None] * inv
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _shared_block(sp, gp, m, h, e, quant):
    L = h.shape[0]
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = sp["attn"]
    u = _rms(jnp.concatenate([h, e], -1), sp["norm_in"], m["rms_eps"])
    q = _rope((u @ _mat(a["wq"], quant)).reshape(L, H, D), m["rope_theta"])
    k = _rope((u @ _mat(a["wk"], quant)).reshape(L, Hkv, D), m["rope_theta"])
    v = (u @ _mat(a["wv"], quant)).reshape(L, Hkv, D)
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k) * m["attn_scale"]
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    att = o.reshape(L, H * D) @ _mat(a["wo"], quant)
    x = _rms(att, sp["norm_ff"], m["rms_eps"])
    f = sp["mlp"]
    gu = jnp.concatenate([x @ _mat(f["w_gate"], quant),
                          x @ _mat(f["w_up"], quant)], -1)
    gu = gu + (x @ _mat(gp["lora_a"], quant)) @ _mat(gp["lora_b"], quant)
    g, up = jnp.split(gu, 2, axis=-1)
    act = (jax.nn.gelu(g, approximate=False) if m["mlp_act"] == "gelu"
           else jax.nn.silu(g))
    return ((act * up) @ _mat(f["w_down"], quant)) @ _mat(gp["linear"],
                                                           quant)


def _mamba(p, m, x, quant):
    L, d = x.shape
    d_in = m["mamba_expand"] * d
    P, N, G = m["mamba_headdim"], m["mamba_d_state"], m["mamba_ngroups"]
    H = d_in // P
    W = m["mamba_d_conv"]
    zxbcdt = x @ _mat(p["w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * G * N],
                  zxbcdt[:, 2 * d_in + 2 * G * N:])
    pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), F32), xbc], 0)
    cw = jnp.asarray(p["conv_w"], F32)
    conv = sum(pad[i:i + L] * cw[i] for i in range(W))
    xbc = jax.nn.silu(conv + jnp.asarray(p["conv_b"], F32))
    xs = xbc[:, :d_in].reshape(L, H, P)
    grp = np.arange(H) // (H // G)
    Bm = xbc[:, d_in:d_in + G * N].reshape(L, G, N)[:, grp]      # (L,H,N)
    Cm = xbc[:, d_in + G * N:].reshape(L, G, N)[:, grp]
    dt = jax.nn.softplus(dt + jnp.asarray(p["dt_bias"], F32))   # (L,H)
    A = -jnp.exp(jnp.asarray(p["a_log"], F32))

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return S, jnp.einsum("hn,hnp->hp", c_t, S)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P), F32), (xs, Bm, Cm, dt))
    y = y + jnp.asarray(p["d_skip"], F32)[:, None] * xs
    y = y.reshape(L, d_in) * jax.nn.silu(z)
    y = _rms(y.reshape(L, G, d_in // G),
             jnp.asarray(p["norm"]).reshape(G, d_in // G), m["rms_eps"])
    return y.reshape(L, d_in) @ _mat(p["w_out"], quant)


def _layers(m):
    """The program's groups in layer order: ("mamba", n) runs and
    ("hybrid", j) layers, from ``hybrid_layer_ids`` alone."""
    out = []
    for layer in range(m["n_layers"]):
        if layer in m["hybrid_layer_ids"]:
            out.append(("hybrid", m["hybrid_layer_ids"].index(layer)))
        elif out and out[-1][0] == "mamba":
            out[-1] = ("mamba", out[-1][1] + 1)
        else:
            out.append(("mamba", 1))
    return out


@functools.partial(jax.jit, static_argnames=("m_items", "quant"))
def _final_hidden(params, tokens, m_items, quant):
    m = dict(m_items)
    e = jnp.asarray(params["embed"], F32)[tokens]
    if quant == "fp8":
        e = _fp8(e, axis=-1)
    h = e

    def mamba_layer(h, lp):
        x = _rms(h, lp["norm"], m["rms_eps"])
        return h + _mamba(lp["mamba"], m, x, quant), None

    for gi, (kind, n) in enumerate(_layers(m)):
        gp = params["groups"][gi]
        if kind == "mamba":
            h = jax.lax.scan(mamba_layer, h, gp)[0]
            continue
        sp = params["shared"][n % m["num_mem_blocks"]]
        t = _shared_block(sp, gp, m, h, e, quant)
        lp = jax.tree.map(lambda a: a[0], gp["layers"])
        h = h + _mamba(lp["mamba"], m, _rms(h + t, lp["norm"], m["rms_eps"]),
                       quant)
    return _rms(h, params["final_norm"], m["rms_eps"])


def _items(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def logits_at(params, model: dict, tokens, positions, quant=None,
              pad_to: int = 0):
    """Reference logits (len(positions), V) of one sequence ``tokens`` at
    ``positions``, right-padded to ``pad_to`` (causal: the pad reaches no
    real position)."""
    L = max(len(tokens), pad_to)
    padded = np.zeros(L, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = _final_hidden(params, jnp.asarray(padded), _items(model), quant)
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        if model["tie_embeddings"]:
            return rows @ _mat(params["embed"].T, quant)
        return rows @ _mat(params["lm_head"], quant)


# -- the program and the counts ------------------------------------------------

def check_program(cfg, conf: dict) -> None:
    """The program's registered configuration has to be the file's, and
    its draft tree the file's chain."""
    from repro.launch.specs import tree_for
    from repro.models.model import hybrid_scale
    m, d = conf["model"], conf["draft"]
    s = cfg.ssm
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
           "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
           "rms_eps": cfg.rms_eps, "tie_embeddings": cfg.tie_embeddings,
           "hybrid_layer_ids": list(cfg.hybrid_layer_ids),
           "num_mem_blocks": cfg.num_mem_blocks,
           "adapter_rank": cfg.adapter_rank,
           "attn_scale": hybrid_scale(cfg), "mlp_act": "gelu",
           "mamba_expand": s.expand,
           "mamba_headdim": s.head_dim, "mamba_d_state": s.d_state,
           "mamba_ngroups": s.n_groups, "mamba_d_conv": s.conv_width,
           "chunk_size": s.chunk_size, "block_kind": cfg.block_kind,
           "dtype": cfg.dtype}
    want = dict(m, dtype=conf["dtype"], block_kind="mamba2")
    for k, v in got.items():
        w = want[k]
        if (v != w if not isinstance(w, float)
                else not np.isclose(v, w, rtol=1e-6)):
            raise SystemExit(f"program config {cfg.name}.{k}={v!r}, file "
                             f"says {w!r}")
    dc = cfg.draft
    if (dc.kind, dc.n_heads, dc.n_mlp_layers, dc.prefix_attention) != (
            d["kind"], d["n_heads"], d["n_mlp_layers"], d["prefix_attention"]):
        raise SystemExit(f"program draft {dc} != file {d}")
    depth = np.asarray(tree_for(cfg).depth)
    nodes = np.bincount(depth, minlength=len(d["tree_nodes_per_depth"]))
    if nodes.tolist() != d["tree_nodes_per_depth"]:
        raise SystemExit(f"program tree has {nodes.tolist()} nodes by depth, "
                         f"the file {d['tree_nodes_per_depth']}")


def _mamba_params(m) -> int:
    d = m["d_model"]
    d_in = m["mamba_expand"] * d
    H = d_in // m["mamba_headdim"]
    proj = 2 * d_in + 2 * m["mamba_ngroups"] * m["mamba_d_state"] + H
    return d * proj + d_in * d


def _block_params(m) -> int:
    d, H, D = m["d_model"], m["n_heads"], m["head_dim"]
    return (2 * d * (H + 2 * m["n_kv_heads"]) * D + H * D * d
            + 3 * d * m["d_ff"])


def _invocation_params(m) -> int:
    return m["adapter_rank"] * (m["d_model"] + 2 * m["d_ff"]) + m["d_model"] ** 2


def flops_per_live_row(model: dict, draft: dict) -> int:
    """Matmul FLOPs of one live row of a verify step: the chain's T tokens
    through every layer's matrices (each hybrid layer through its block's,
    its adapter's and its own) and the tied output head, and the draft
    heads (no prefix attention).  The scans and attention over the cache
    are left out, so it is a lower bound."""
    m, d, V = model, model["d_model"], model["vocab_size"]
    per_token = (m["n_layers"] * _mamba_params(m) + d * V
                 + len(m["hybrid_layer_ids"]) * (_block_params(m)
                                                 + _invocation_params(m)))
    T = sum(draft["tree_nodes_per_depth"])
    heads = sum(n * 2 * ((k + 1) * d * d + (draft["n_mlp_layers"] - 1) * d * d
                         + d * V)
                for k, n in enumerate(draft["tree_nodes_per_depth"]) if k)
    return 2 * T * per_token + heads


def tree_work(cached, T_pad: int, model: dict):
    """One paged tree-kernel call: one invocation of a shared block."""
    return tree_attn.work(cached, T_pad, model["n_heads"],
                          model["n_kv_heads"], model["head_dim"])


def ssd_verify_work(live_rows: float, model: dict, draft: dict):
    """(flops, bytes) of one verify step's Mamba2 chain, every layer, over
    ``live_rows`` rows, as a lower bound of what its two kernels do: the
    verify pass reads each live row's float32 SSD state once, its T
    tokens' x, B and C (served dtype, 2 bytes) and dt (float32), and
    writes y (float32); the commit pass reads the state, the tokens' x,
    B and dt again, and writes the state.  FLOPs: per token, head and
    state entry, the decay and the outer product's multiply-add (3),
    and in the verify pass the readout's multiply-add (2)."""
    m = model
    d_in = m["mamba_expand"] * m["d_model"]
    H = d_in // m["mamba_headdim"]
    N, G = m["mamba_d_state"], m["mamba_ngroups"]
    T = sum(draft["tree_nodes_per_depth"])
    state = N * d_in * 4
    verify = state + T * (2 * (d_in + 2 * G * N) + 4 * H + 4 * d_in)
    commit = 2 * state + T * (2 * (d_in + G * N) + 4 * H)
    L = m["n_layers"]
    return (8 * T * N * d_in * live_rows * L,
            (verify + commit) * live_rows * L)
