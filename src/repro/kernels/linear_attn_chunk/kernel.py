"""Pallas TPU chunked decay linear attention (RWKV6 / Mamba2-SSD shared).

Implements (per head):
    S_t = Diag(exp(w_t)) S_{t-1} + k_t v_t^T
    o_t = r_t S_{t-1} + (r_t . (u * k_t)) v_t

as chunk-parallel intra-chunk matmuls + a sequential inter-chunk state
recurrence carried in VMEM scratch across the (sequential) chunk grid axis.

Grid: (B, H, S/c) with the chunk axis 'arbitrary'. Working set per step:
four (c, d) tiles + (c, c) logits + (d, d) state — c=d=64..128 keeps this
well under VMEM, and all matmul dims are 64/128-aligned for the MXU.

Numerics: fp32 throughout; cumulative in-chunk log-decay is clamped at
LOG_DECAY_CLAMP (exp(-lcw) <= e^20 ≈ 5e8, safe in fp32) — matching the
pure-jnp chunked path in repro.models.ssm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LOG_DECAY_CLAMP = -20.0


def _chunk_body(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_sc, *,
                c: int, use_u: bool):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    r = r_ref[0, 0].astype(jnp.float32)          # (c, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)          # (c, dv)
    w = w_ref[0, 0].astype(jnp.float32)          # (c, dk) log-decay <= 0

    lcw = jnp.cumsum(w, axis=0)                  # inclusive
    lcw_excl = lcw - w
    q_eff = r * jnp.exp(lcw_excl)
    # intra-chunk coefficients PAIRWISE: E[t,s,d] = exp(lcw_excl[t]-lcw[s]),
    # every exponent <= 0 for s < t => overflow-free (vs factorized exp).
    # (c, c, dk) tile: 64^3 * 4B = 1 MiB, fits VMEM comfortably.
    dlt = lcw_excl[:, None, :] - lcw[None, :, :]
    E = jnp.exp(jnp.minimum(dlt, 0.0))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * E, axis=-1)         # (c, c)
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(si < ti, A, 0.0)               # strict lower triangle
    o = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())))          # (c, dv)
    if use_u:
        u = u_ref[0].astype(jnp.float32)         # (dk,)
        diag = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True)
        o = o + diag * v
    # inter-chunk: contribution of carried state
    o = o + jax.lax.dot_general(q_eff, s_sc[...], (((1,), (0,)), ((), ())))
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update
    lcw_c = lcw[-1:, :]                          # (1, dk)
    k2 = k * jnp.exp(lcw_c - lcw)
    s_sc[...] = (s_sc[...] * jnp.exp(lcw_c[0])[:, None]
                 + jax.lax.dot_general(k2, v, (((0,), (0,)), ((), ()))))


@functools.partial(jax.jit, static_argnames=("chunk", "use_u", "interpret"))
def linear_attn_chunk(r, k, v, w_log, u=None, *, chunk: int = 64,
                      use_u: bool = True, interpret: bool | None = None):
    """r/k/w_log: (B,H,S,dk); v: (B,H,S,dv); u: (H,dk). Returns o (B,H,S,dv).

    S must be a chunk multiple (ops.py pads).
    interpret: None => auto (compile on TPU, interpret elsewhere)."""
    interpret = resolve_interpret(interpret)
    B, H, S, dk = k.shape
    dv = v.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    if u is None:
        u = jnp.zeros((H, dk), jnp.float32)
        use_u = False

    body = functools.partial(_chunk_body, c=chunk, use_u=use_u)
    return pl.pallas_call(
        body,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, dk), lambda b, h, j: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dv), lambda b, h, j: (b, h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w_log, u)
