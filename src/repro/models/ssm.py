"""SSM / linear-attention layers: Mamba2 (SSD) and RWKV6 (Finch).

Both reduce to *decay linear attention*:

    S_t = Diag(exp(w_t)) S_{t-1} + k_t v_t^T          (w_t = log-decay <= 0)
    o_t = r_t S_{t-1} + (r_t . (u * k_t)) v_t         (u: per-head bonus)

RWKV6: per-token per-channel decay, u = learned bonus.
Mamba2: per-token per-head scalar decay a_t = exp(dt_t * A_h); readout uses
S_t, which maps onto the same primitive via r' = r * a_t and u = 1.

Two execution paths share the math:
  * ``decay_attention_chunked`` / ``mamba2_ssd_chunked`` — train/prefill:
    chunked scan (intra-chunk matmul + inter-chunk state recurrence).
    Mirrored by the Pallas kernel ``repro.kernels.linear_attn_chunk``.
  * verify: RWKV6's ``decay_attention_seq`` RETURNS every per-token state
    so chain-speculative verification can roll back to the last accepted
    token without recompute; Mamba2's chain runs through the Pallas
    kernels ``repro.kernels.ssd_chain``, whose commit replays the
    accepted prefix instead (serving/cache.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, group_norm, rms_norm

LOG_DECAY_CLAMP = -20.0  # per-chunk cumulative log-decay clamp (see DESIGN)


def _pad_mask(valid_len, B, T):
    """(B, T) bool: position < valid_len.  None => all valid."""
    if valid_len is None:
        return None
    return jnp.arange(T)[None, :] < valid_len[:, None]


def _mask_decay_inputs(mask, w_log, k):
    """Length-masked scan (DESIGN.md §8): force log-decay 0 (decay 1) and
    key 0 at right-pad positions, so the recurrent state is carried past
    pads UNCHANGED — the same trick the chunked scans already use for
    their own chunk-multiple padding, so a masked pad tail is bitwise
    indistinguishable from tail padding and bucketed/chunked prefill
    stays byte-exact for recurrent archs."""
    if mask is None:
        return w_log, k
    m = mask[..., None] if k.ndim == 3 else mask[:, :, None, None]
    mw = mask[..., None] if w_log.ndim == 3 else mask[:, :, None, None]
    return jnp.where(mw, w_log, 0.0), jnp.where(m, k, 0.0)


def _gather_last_valid(x, valid_len):
    """x: (B, T, ...) -> (B, 1, ...) at per-row index valid_len - 1
    (plain ``x[:, -1:]`` when valid_len is None)."""
    if valid_len is None:
        return x[:, -1:]
    idx = jnp.clip(valid_len - 1, 0, x.shape[1] - 1)
    idx = idx.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx, axis=1)


# ---------------------------------------------------------------------------
# decay linear attention primitives
# ---------------------------------------------------------------------------


def decay_attention_chunked(r, k, v, w_log, u=None, initial_state=None,
                            chunk: int = 64, scalar_decay: bool = False):
    """r/k: (B,S,H,dk); v: (B,S,H,dv); u: (H,dk) or None.
    w_log: (B,S,H,dk), or (B,S,H,1) with scalar_decay=True (Mamba2: one
    decay per head per token — the intra-chunk coefficient then factors out
    of the d_k contraction, shrinking the working set by d_k; see §Perf).

    Returns (o: (B,S,H,dv), final_state: (B,H,dk,dv)).
    """
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    dw = w_log.shape[-1]
    assert dw == dk or (scalar_decay and dw == 1)
    S_orig = S
    if S % chunk:
        # pad to a chunk multiple: k=0 / w_log=0 (decay 1) is exact
        pad = chunk - S % chunk
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, w_log = z(r), z(k), z(v), z(w_log)
        S = S + pad
    nc = S // chunk

    rf = r.astype(jnp.float32).reshape(B, nc, chunk, H, dk)
    kf = k.astype(jnp.float32).reshape(B, nc, chunk, H, dk)
    vf = v.astype(jnp.float32).reshape(B, nc, chunk, H, dv)
    wf = w_log.astype(jnp.float32).reshape(B, nc, chunk, H, dw)

    # (nc, B, chunk, H, d*)
    rf, kf, vf, wf = (jnp.swapaxes(t, 0, 1) for t in (rf, kf, vf, wf))

    if initial_state is None:
        S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    else:
        S0 = initial_state.astype(jnp.float32)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)  # strict lower

    def body(state, xs):
        rc, kc, vc, wc = xs                               # (B,c,H,d*)
        lcw = jnp.cumsum(wc, axis=1)                      # inclusive
        lcw_excl = lcw - wc
        q_eff = rc * jnp.exp(lcw_excl)                    # decays, <= |rc|
        # intra-chunk coefficients, PAIRWISE so every exponent is <= 0
        # (a factorized exp(lcw_t)*exp(-lcw_s) overflows for strong decays)
        if scalar_decay:
            # Mamba2: decay is per-head SCALAR — the pairwise factor pulls
            # out of the d_k contraction: A = (r k^T) * exp(Δ), Δ (t,s,H).
            dlt = lcw_excl[:, :, None, :, 0] - lcw[:, None, :, :, 0]
            E = jnp.exp(jnp.minimum(dlt, 0.0))            # (B,t,s,H)
            A = jnp.einsum("bthd,bshd->bhts", rc, kc) * \
                jnp.transpose(E, (0, 3, 1, 2))
        else:
            # E[t,s,h,d] = exp(lcw_excl[t,d] - lcw[s,d]),  s < t
            dlt = lcw_excl[:, :, None] - lcw[:, None, :, :, :]
            E = jnp.exp(jnp.minimum(dlt, 0.0))            # (B,t,s,H,dk)
            A = jnp.einsum("bthd,bshd,btshd->bhts", rc, kc, E)
        A = jnp.where(tri[None, None], A, 0.0)
        o = jnp.einsum("bhts,bshd->bthd", A, vc)
        if u is not None:
            diag = jnp.einsum("bthd,bthd->bth",
                              rc * u.astype(jnp.float32)[None, None], kc)
            o = o + diag[..., None] * vc
        # inter-chunk (contribution of carried state)
        o = o + jnp.einsum("bthd,bhdv->bthv", q_eff, state)
        # state update
        lcw_c = lcw[:, -1:]                               # (B,1,H,dk)
        k2 = kc * jnp.exp(lcw_c - lcw)
        state = state * jnp.exp(lcw_c[:, 0])[..., None] + jnp.einsum(
            "bshd,bshv->bhdv", k2, vc)
        return state, o

    state, o = jax.lax.scan(body, S0, (rf, kf, vf, wf))
    o = jnp.swapaxes(o, 0, 1).reshape(B, S, H, dv)[:, :S_orig]
    return o.astype(v.dtype), state


def decay_attention_seq(r, k, v, w_log, u=None, initial_state=None):
    """Per-token scan (RWKV6 verify); returns (o, states_per_token
    (B,T,H,dk,dv)), o_t = r_t S_{t-1} + (r_t.(u*k_t)) v_t."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    if initial_state is None:
        S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    else:
        S0 = initial_state.astype(jnp.float32)
    rf = jnp.moveaxis(r.astype(jnp.float32), 1, 0)
    kf = jnp.moveaxis(k.astype(jnp.float32), 1, 0)
    vf = jnp.moveaxis(v.astype(jnp.float32), 1, 0)
    wf = jnp.moveaxis(w_log.astype(jnp.float32), 1, 0)

    def body(state, xs):
        rt, kt, vt, wt = xs                               # (B,H,d*)
        o = jnp.einsum("bhd,bhdv->bhv", rt, state)
        if u is not None:
            o = o + jnp.einsum(
                "bhd,bhd->bh", rt * u.astype(jnp.float32)[None],
                kt)[..., None] * vt
        state = state * jnp.exp(wt)[..., None] + \
            kt[..., None] * vt[:, :, None]
        return state, (o, state)

    _, (o, states) = jax.lax.scan(body, S0, (rf, kf, vf, wf))
    o = jnp.moveaxis(o, 0, 1).astype(v.dtype)             # (B,T,H,dv)
    states = jnp.moveaxis(states, 0, 1)                   # (B,T,H,dk,dv)
    return o, states


def mamba2_ssd_chunked(r, k, v, w_log, initial_state=None, chunk: int = 64):
    """Grouped SSD chunked scan (Mamba2 full/train path, §Perf iter 2).

    Exploits Mamba2's structure: B (k) and C (r) are SHARED by the heads
    of a group (head i reads group i // (H / G)), decay is a per-head
    scalar — so the (c, c) score matrix is computed ONCE per group instead
    of per head, and k/r are never broadcast-materialized across heads.

    r/k: (B, S, G, ds); v: (B, S, H, hd); w_log: (B, S, H) per-head scalar
    log-decay (<= 0).  Readout is o_t = C_t · h_t with
    h_t = a_t h_{t-1} + B_t v_t  (state INCLUSIVE of token t).
    Returns (o: (B, S, H, hd), final_state: (B, H, ds, hd)).
    """
    B, S, G, ds = k.shape
    H, hd = v.shape[2], v.shape[3]
    Hg = H // G
    S_orig = S
    if S % chunk:
        pad = chunk - S % chunk
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w_log = jnp.pad(w_log, ((0, 0), (0, pad), (0, 0)))
        S += pad
    nc = S // chunk
    rf = r.astype(jnp.float32).reshape(B, nc, chunk, G, ds).swapaxes(0, 1)
    kf = k.astype(jnp.float32).reshape(B, nc, chunk, G, ds).swapaxes(0, 1)
    vf = v.astype(jnp.float32).reshape(B, nc, chunk, G, Hg, hd).swapaxes(0, 1)
    wf = w_log.astype(jnp.float32).reshape(B, nc, chunk, G, Hg).swapaxes(0, 1)
    if initial_state is None:
        S0 = jnp.zeros((B, G, Hg, ds, hd), jnp.float32)
    else:
        S0 = initial_state.astype(jnp.float32).reshape(B, G, Hg, ds, hd)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))        # INCLUSIVE diag

    def body(state, xs):
        rc, kc, vc, wc = xs
        lcw = jnp.cumsum(wc, axis=1)                      # (B,c,G,Hg) inclusive
        A0 = jnp.einsum("btgd,bsgd->bgts", rc, kc)        # group-shared
        E = jnp.exp(jnp.minimum(lcw[:, :, None] - lcw[:, None, :], 0.0))
        E = jnp.where(tri[None, :, :, None, None], E, 0.0)  # (B,t,s,G,Hg)
        o = jnp.einsum("bgts,btsgh,bsghv->btghv", A0, E, vc)
        # inter-chunk: o_t += exp(lcw_t) * (r_t . S0)
        rS = jnp.einsum("btgd,bghdv->btghv", rc, state)
        o = o + jnp.exp(lcw)[..., None] * rS
        # state update
        lcw_c = lcw[:, -1:]                               # (B,1,G,Hg)
        dec = jnp.exp(lcw_c - lcw)                        # (B,c,G,Hg)
        state = state * jnp.exp(lcw_c[:, 0])[..., None, None] + jnp.einsum(
            "bsgh,bsgd,bsghv->bghdv", dec, kc, vc)
        return state, o

    state, o = jax.lax.scan(body, S0, (rf, kf, vf, wf))
    o = jnp.swapaxes(o, 0, 1).reshape(B, S, H, hd)[:, :S_orig]
    return o.astype(v.dtype), state.reshape(B, H, ds, hd)


# ---------------------------------------------------------------------------
# Mamba2 (SSD) layer
# ---------------------------------------------------------------------------


def mamba2_dims(cfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return d_in, n_heads, conv_ch


def ssd_state_shape(cfg):
    """One row's SSD state: ``(d_state, H * head_dim)`` — the state row of
    every head side by side along the lanes, so that no axis of it falls
    short of the TPU's 128 lanes (``kernels/ssd_chain``)."""
    s = cfg.ssm
    _, H, _ = mamba2_dims(cfg)
    return (s.d_state, H * s.head_dim)


def init_mamba2(key, cfg, dtype):
    s = cfg.ssm
    d = cfg.d_model
    d_in, H, conv_ch = mamba2_dims(cfg)
    ks = jax.random.split(key, 4)
    return {
        "w_in": dense_init(ks[0], d, d_in + conv_ch + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_ch)) * 0.1
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.zeros((H,), jnp.float32),            # A = -exp(a_log)
        "d_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), np.log(np.e - 1), jnp.float32),
        "norm": jnp.zeros((d_in,), dtype),
        "w_out": dense_init(ks[2], d_in, d, dtype),
    }


def _causal_conv(x, w, b, conv_state=None):
    """Depthwise causal conv. x: (B,T,C); w: (W,C). conv_state: (B,W-1,C).

    Returns (y (B,T,C), windows (B,T,W-1,C)) where windows[t] is the conv
    state AFTER consuming token t (the last W-1 inputs ending at t).
    """
    W = w.shape[0]
    B, T, C = x.shape
    if conv_state is None:
        conv_state = jnp.zeros((B, W - 1, C), x.dtype)
    xp = jnp.concatenate([conv_state, x], axis=1)         # (B, T+W-1, C)
    idx = jnp.arange(T)[:, None] + jnp.arange(W)[None, :]  # (T, W)
    patches = xp[:, idx]                                  # (B,T,W,C)
    y = jnp.einsum("btwc,wc->btc", patches.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    windows = patches[:, :, 1:, :]                        # state after t
    return y.astype(x.dtype), windows


def mamba2_fwd(p, cfg, x, *, mode: str, ssd_state=None, conv_state=None,
               chunk: int | None = None, valid_len=None):
    """mode: 'full' (train/prefill, chunked) | 'verify' (per-token states).

    Returns (out, new_states) where new_states =
      full:   {'ssd_state': (B,ds,H*hd) final, 'conv_win': (B,W-1,C) final}
      verify: {'ssd_state': the chain's inputs (a dict, per token), which
               the commit replays from the state (serving/cache.py),
               'conv_win': (B,T,W-1,C) per token}

    The SSD state is kept in the ``ssd_state_shape`` layout.  B and C come
    in ``n_groups`` groups (head i reads group i // (H / n_groups)), and
    the gated RMSNorm is taken per group.  Verify runs the chain through
    ``kernels/ssd_chain``.

    ``valid_len`` (B,), full mode only: right-pad positions >= valid_len
    are length-masked out of the scan (decay 1, key 0 — state carried past
    pads unchanged) and the returned final states are those after token
    ``valid_len - 1``, which is what lets bucketed/chunked prefill pad
    recurrent archs (DESIGN.md §8).
    """
    s = cfg.ssm
    d_in, H, conv_ch = mamba2_dims(cfg)
    B, T, _ = x.shape
    hd, ds, G = s.head_dim, s.d_state, s.n_groups

    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_ch]
    dt_raw = zxbcdt[..., d_in + conv_ch:]

    xbc, conv_windows = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(B, T, H, hd)
    Bmat = xbc[..., d_in:d_in + G * ds].reshape(B, T, G, ds)
    Cmat = xbc[..., d_in + G * ds:].reshape(B, T, G, ds)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])                              # (H,) negative

    if mode == "full":
        # grouped SSD: B/C shared by a group's heads — never broadcast
        v = xs.astype(jnp.float32) * dt[..., None]        # (B,T,H,hd)
        w_m, B_m = _mask_decay_inputs(_pad_mask(valid_len, B, T),
                                      dt * A, Bmat.astype(jnp.float32))
        s0 = None
        if ssd_state is not None:
            s0 = ssd_state.reshape(B, ds, H, hd).transpose(0, 2, 1, 3)
        o, final_state = mamba2_ssd_chunked(
            Cmat.astype(jnp.float32), B_m, v, w_m,
            initial_state=s0, chunk=chunk or s.chunk_size)
        new_states = {"ssd_state": final_state.transpose(0, 2, 1, 3).reshape(
                          B, ds, H * hd),
                      "conv_win": _gather_last_valid(conv_windows,
                                                     valid_len)[:, 0]}
    else:
        from repro.kernels.ssd_chain.ops import ssd_chain_verify_bthd
        if ssd_state is None:
            ssd_state = jnp.zeros((B, ds, H * hd), jnp.float32)
        o, chain = ssd_chain_verify_bthd(Cmat, Bmat, xs, dt, A, ssd_state)
        new_states = {"ssd_state": chain, "conv_win": conv_windows}

    y = o.astype(jnp.float32) + p["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, T, d_in).astype(x.dtype) * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, T, G, d_in // G),
                 p["norm"].reshape(G, d_in // G), cfg.rms_eps)
    return y.reshape(B, T, d_in) @ p["w_out"], new_states


# ---------------------------------------------------------------------------
# RWKV6 layer (time-mix + channel-mix)
# ---------------------------------------------------------------------------

RWKV_LORA = 32
RWKV_LORA_W = 64


def init_rwkv6(key, cfg, dtype):
    d, dff = cfg.d_model, cfg.d_ff
    H = cfg.n_heads
    hd = d // H
    ks = jax.random.split(key, 12)
    lin = lambda i, a, b: dense_init(ks[i], a, b, dtype)
    return {
        # time-mix ddlerp: mu_x + per-target mus + lora (5 targets: w,k,v,r,g)
        "tm_mu_x": jnp.zeros((d,), dtype),
        "tm_mu": jnp.zeros((5, d), dtype),
        "tm_lora_a": lin(0, d, 5 * RWKV_LORA),
        "tm_lora_b": (jax.random.normal(ks[1], (5, RWKV_LORA, d)) * 0.01
                      ).astype(dtype),
        # decay
        "w0": jnp.full((d,), -1.0, jnp.float32),
        "w_lora_a": lin(2, d, RWKV_LORA_W),
        "w_lora_b": (jax.random.normal(ks[3], (RWKV_LORA_W, d)) * 0.01
                     ).astype(dtype),
        "u_bonus": jnp.zeros((H, hd), jnp.float32),
        "wr": lin(4, d, d), "wk": lin(5, d, d), "wv": lin(6, d, d),
        "wg": lin(7, d, d), "wo": lin(8, d, d),
        "gn_gamma": jnp.ones((d,), jnp.float32),
        "gn_beta": jnp.zeros((d,), jnp.float32),
        # channel-mix
        "cm_mu_k": jnp.zeros((d,), dtype),
        "cm_mu_r": jnp.zeros((d,), dtype),
        "cm_wk": lin(9, d, dff), "cm_wv": lin(10, dff, d),
        "cm_wr": lin(11, d, d),
    }


def _token_shift(x, last):
    """last: (B,1,d) previous token (zeros at seq start)."""
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def rwkv6_timemix(p, cfg, x, *, mode: str, wkv_state=None, shift_last=None,
                  chunk: int = 64, valid_len=None):
    """``valid_len`` (B,), full mode only: length-mask the wkv scan past
    right-pads and take the shift state at ``valid_len - 1`` (see
    ``mamba2_fwd``)."""
    B, T, d = x.shape
    H = cfg.n_heads
    hd = d // H
    if shift_last is None:
        shift_last = jnp.zeros((B, 1, d), x.dtype)
    xx = _token_shift(x, shift_last) - x

    z = x + xx * p["tm_mu_x"]
    lora = jnp.tanh(z @ p["tm_lora_a"]).reshape(B, T, 5, RWKV_LORA)
    mix = p["tm_mu"][None, None] + jnp.einsum("btfr,frd->btfd", lora,
                                              p["tm_lora_b"].astype(x.dtype))
    xw, xk, xv, xr, xg = [x + xx * mix[:, :, i] for i in range(5)]

    w_log = -jnp.exp(p["w0"] + jnp.tanh(xw.astype(jnp.float32) @
                                        p["w_lora_a"].astype(jnp.float32))
                     @ p["w_lora_b"].astype(jnp.float32))   # (B,T,d) <= 0
    r = (xr @ p["wr"]).reshape(B, T, H, hd)
    k = (xk @ p["wk"]).reshape(B, T, H, hd)
    v = (xv @ p["wv"]).reshape(B, T, H, hd)
    g = xg @ p["wg"]
    w_log = w_log.reshape(B, T, H, hd)

    if mode == "full":
        w_m, k_m = _mask_decay_inputs(_pad_mask(valid_len, B, T), w_log, k)
        o, final_state = decay_attention_chunked(
            r, k_m, v, w_m, u=p["u_bonus"], initial_state=wkv_state,
            chunk=chunk)
        new = {"wkv_state": final_state,
               "shift_tm": _gather_last_valid(x, valid_len)}
    else:
        o, states = decay_attention_seq(r, k, v, w_log, u=p["u_bonus"],
                                        initial_state=wkv_state)
        # per-token candidates; keep the singleton time axis so commit
        # (select along T) yields the committed layout (B, 1, d)
        new = {"wkv_state": states, "shift_tm": x[:, :, None, :]}
    o = group_norm(o.reshape(B, T, d), p["gn_gamma"], p["gn_beta"], H,
                   eps=64e-5)
    return (o * jax.nn.silu(g)) @ p["wo"], new


def rwkv6_chanmix(p, x, *, shift_last=None):
    B, T, d = x.shape
    if shift_last is None:
        shift_last = jnp.zeros((B, 1, d), x.dtype)
    xx = _token_shift(x, shift_last) - x
    xk = x + xx * p["cm_mu_k"]
    xr = x + xx * p["cm_mu_r"]
    kk = jnp.square(jax.nn.relu(xk @ p["cm_wk"]))
    return jax.nn.sigmoid(xr @ p["cm_wr"]) * (kk @ p["cm_wv"])
