"""Pallas TPU kernels of the Mamba2 chain verify: T chain tokens through
the SSD recurrence, in two passes.

``ssd_chain_verify`` (the verify forward) reads each row's state, runs
the chain in VMEM and writes the per-token outputs; it writes no state.
``ssd_chain_commit`` (after acceptance) replays the accepted tokens from
the same state and writes the committed one: tokens past a row's
accepted prefix come in masked (decay 1, input 0), so they leave the
state exactly as it was.  Keeping every token's candidate state instead
would hold T states of every layer (3.5 GB at zamba2-7b's 16 rows) live
until the commit, which the chip's memory does not have room for.

Grid ``(rows, H·hd / L)``: one program per (row, block of L lanes, all in
one B/C group).  A row's state is ``(d_state, H·hd)`` — every head's state
rows side by side along the lanes, the layout of
``models.ssm.ssd_state_shape`` — and a program's ``(d_state, L)`` slab of
it is held in VMEM across the chain:

    S_t = a_t * S_{t-1} + B_t v_t       a_t, v_t: (1, L) lanes; B_t: (ds, 1)
    y_t = sum over d_state of C_t * S_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _step(a_ref, v_ref, b_ref, s, t):
    return a_ref[0, pl.ds(t, 1)] * s + b_ref[0, 0, t] * v_ref[0, pl.ds(t, 1)]


def _verify_body(a_ref, v_ref, b_ref, c_ref, s0_ref, y_ref, s_sc, *, T: int):
    s_sc[...] = s0_ref[0]                                     # (ds, L)
    for t in range(T):
        s = _step(a_ref, v_ref, b_ref, s_sc[...], t)
        s_sc[...] = s
        y_ref[0, pl.ds(t, 1)] = jnp.sum(c_ref[0, 0, t] * s, axis=0,
                                        keepdims=True)


def _commit_body(a_ref, v_ref, b_ref, s0_ref, s_ref, *, T: int):
    s_ref[0] = s0_ref[0]
    for t in range(T):
        s_ref[0] = _step(a_ref, v_ref, b_ref, s_ref[0], t)


def block_lanes(per_group: int, max_lanes: int = 2048) -> int:
    """Lanes of one program: the most that divide a group's lanes, are a
    whole number of 128-lane tiles and at most ``max_lanes`` (the slab
    stays well inside scoped VMEM); a group's lanes where none is.  The
    recurrence is lane-wise, so a block need not end on a head."""
    fits = [n for n in range(128, min(per_group, max_lanes) + 1, 128)
            if per_group % n == 0]
    return max(fits) if fits else per_group


def _specs(T, ds, lanes, per_group):
    """Block specs of (a and v, B or C, a state): a row's T tokens, its
    group's B/C columns and its state slab, for the program's heads."""
    if per_group % lanes:
        raise ValueError(f"{lanes} lanes a program do not tile a group's "
                         f"{per_group}")
    blocks_per_group = per_group // lanes
    tokens = pl.BlockSpec((1, T, lanes), lambda b, h: (b, 0, h))
    group = pl.BlockSpec((1, 1, T, ds, 1),
                         lambda b, h: (b, h // blocks_per_group, 0, 0, 0))
    state = pl.BlockSpec((1, ds, lanes), lambda b, h: (b, 0, h))
    return tokens, group, state


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def ssd_chain_verify(a, v, Bk, Ck, s0, *, lanes: int,
                     interpret: bool | None = None):
    """Kernel layout.  a/v: (R, T, H*hd) f32 — each head's decay
    exp(dt A) repeated over its lanes, and dt * x; Bk/Ck: (R, G, T, ds, 1)
    f32; s0: (R, ds, H*hd) f32.  ``lanes`` a program (``block_lanes``).
    Returns y (R, T, H*hd) f32."""
    R, T, HP = v.shape
    ds = Bk.shape[3]
    tokens, group, state = _specs(T, ds, lanes, HP // Bk.shape[1])
    return pl.pallas_call(
        functools.partial(_verify_body, T=T),
        grid=(R, HP // lanes),
        in_specs=[tokens, tokens, group, group, state],
        out_specs=tokens,
        out_shape=jax.ShapeDtypeStruct((R, T, HP), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ds, lanes), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret),
        # the ops' names in a profile (bench/metrics/ssd_verify_ms.py)
        name="ssd_chain_verify",
    )(a, v, Bk, Ck, s0)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def ssd_chain_commit(a, v, Bk, s0, *, lanes: int,
                     interpret: bool | None = None):
    """The state after the chain, in the layout of ``ssd_chain_verify``
    (a and v masked past each row's accepted tokens by the caller).
    Returns (R, ds, H*hd) f32."""
    R, T, HP = v.shape
    ds = Bk.shape[3]
    tokens, group, state = _specs(T, ds, lanes, HP // Bk.shape[1])
    return pl.pallas_call(
        functools.partial(_commit_body, T=T),
        grid=(R, HP // lanes),
        in_specs=[tokens, tokens, group, state],
        out_specs=state,
        out_shape=jax.ShapeDtypeStruct((R, ds, HP), jnp.float32),
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret),
        name="ssd_chain_commit",
    )(a, v, Bk, s0)
