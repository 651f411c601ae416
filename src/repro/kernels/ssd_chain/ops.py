"""Model-layout wrappers of the chain-verify kernels: what
``models/ssm.py::mamba2_fwd(mode="verify")`` and the recurrent commit
(``serving/cache.py``) call."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd_chain.kernel import (block_lanes, ssd_chain_commit,
                                            ssd_chain_verify)


def _lanes(chain) -> int:
    return block_lanes(chain["v"].shape[-1] // chain["b"].shape[-4])


def ssd_chain_verify_bthd(C, Bm, x, dt, A, state0, *,
                          interpret: bool | None = None):
    """C/Bm: (B,T,G,ds); x: (B,T,H,hd); dt: (B,T,H) f32 (after softplus);
    A: (H,) f32 (negative); state0: (B, ds, H*hd) f32.

    Returns (y (B,T,H,hd) f32 = C_t S_t per head, chain): ``chain`` holds
    the tokens' inputs in kernel layout, which ``ssd_chain_commit_rows``
    replays from ``state0`` once acceptance is known.  Oracle:
    ``ref.ssd_chain_ref``."""
    B, T, H, hd = x.shape
    f32 = jnp.float32
    col = lambda m: m.astype(f32).transpose(0, 2, 1, 3)[..., None]
    chain = {"a": jnp.repeat(jnp.exp(dt * A), hd, axis=-1),    # (B,T,H*hd)
             "v": (x.astype(f32) * dt[..., None]).reshape(B, T, H * hd),
             "b": col(Bm)}                                      # (B,G,T,ds,1)
    y = ssd_chain_verify(chain["a"], chain["v"], chain["b"], col(C),
                         state0.astype(f32), lanes=_lanes(chain),
                         interpret=interpret)
    return y.reshape(B, T, H, hd), chain


def ssd_chain_commit_rows(chain, state0, keep, *,
                          interpret: bool | None = None):
    """The state after each row's first ``keep`` chain tokens.  ``chain``
    from ``ssd_chain_verify_bthd``, with any leading axes (a layer stack)
    before its rows; state0: (..., rows, ds, H*hd); keep: (rows,) int32,
    0 leaves a row's state as it was.  Returns state0's shape."""
    a, v, b = chain["a"], chain["v"], chain["b"]
    lead = v.shape[:-2]                                  # (..., rows)
    T = v.shape[-2]
    live = (jnp.arange(T)[None, :] < keep[:, None])[..., None]  # (rows,T,1)
    a = jnp.where(live, a, 1.0)
    v = jnp.where(live, v, 0.0)
    flat = lambda t: t.reshape((-1,) + t.shape[len(lead):])
    s = ssd_chain_commit(flat(a), flat(v), flat(b), flat(state0),
                         lanes=_lanes(chain), interpret=interpret)
    return s.reshape(state0.shape)
