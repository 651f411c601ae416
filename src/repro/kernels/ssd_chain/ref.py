"""Pure-jnp oracle of ``ssd_chain_verify``: the Mamba2 recurrence over a
chain of T tokens, one token at a time, with every state kept.

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T      S: (d_state, head_dim)
    y_t = C_t S_t

per head; head i reads B/C group i // (H / G).  States are returned in the
layout of ``models.ssm.ssd_state_shape``: (d_state, H * head_dim).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chain_ref(C, Bm, x, dt, A, state0):
    """C/Bm: (B,T,G,ds); x: (B,T,H,hd); dt: (B,T,H) f32; A: (H,) f32;
    state0: (B, ds, H*hd) f32.  Returns (y (B,T,H,hd) f32, states
    (B,T,ds,H*hd) f32)."""
    B, T, H, hd = x.shape
    G, ds = C.shape[2], C.shape[3]
    grp = jnp.arange(H) // (H // G)
    f32 = jnp.float32
    k = jnp.moveaxis(Bm.astype(f32)[:, :, grp], 1, 0)        # (T,B,H,ds)
    r = jnp.moveaxis(C.astype(f32)[:, :, grp], 1, 0)
    v = jnp.moveaxis(x.astype(f32) * dt[..., None], 1, 0)    # (T,B,H,hd)
    a = jnp.moveaxis(jnp.exp(dt * A), 1, 0)                  # (T,B,H)
    s0 = state0.astype(f32).reshape(B, ds, H, hd).transpose(0, 2, 1, 3)

    def body(s, xs):
        kt, rt, vt, at = xs
        s = s * at[..., None, None] + kt[..., None] * vt[:, :, None]
        return s, (jnp.einsum("bhd,bhdv->bhv", rt, s), s)

    _, (y, states) = jax.lax.scan(body, s0, (k, r, v, a))
    y = jnp.moveaxis(y, 0, 1)                                 # (B,T,H,hd)
    states = jnp.moveaxis(states, 0, 1)                       # (B,T,H,ds,hd)
    return y, states.transpose(0, 1, 3, 2, 4).reshape(B, T, ds, H * hd)
