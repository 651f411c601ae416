"""Frozen pre-refactor attention kernels — bit-identity oracles ONLY.

These are verbatim copies of the hand-written Pallas kernels as they
existed before the attention-template refactor (DESIGN.md §11) folded
all four paths into ``kernels/attention_template``.  The template's
instantiations must produce BIT-IDENTICAL outputs to these at the old
default block sizes; ``tests/test_attention_template.py`` asserts it
with ``np.testing.assert_array_equal``.

Do not "fix" or modernize this file: its entire value is that it does
not change when the live kernels do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
NULL_BLOCK = 0


# ---------------------------------------------------------------------------
# flash attention (pre-refactor kernels/flash_attention/kernel.py)
# ---------------------------------------------------------------------------


def _flash_body(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                bq: int, bk: int, scale: float, window: int, causal: bool,
                n_kb: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_sc[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def legacy_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    n_qb, n_kb = S // bq, S // bk
    scale = 1.0 / (D ** 0.5)

    grid = (B, Hq, n_qb, n_kb)
    body = functools.partial(_flash_body, bq=bq, bk=bk, scale=scale,
                             window=window, causal=causal, n_kb=n_kb)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# tree attention, dense + paged (pre-refactor kernels/tree_attention/kernel.py)
# ---------------------------------------------------------------------------


def _init_scratch(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (T, bk|T)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_sc[...] = m_new


def _tree_finish(q, tk_ref, tv_ref, tm_ref, o_ref, m_sc, l_sc, acc_sc):
    k = tk_ref[0, 0].astype(jnp.float32)                     # (T, D)
    v = tv_ref[0, 0].astype(jnp.float32)
    _softmax_update(q, k, v, tm_ref[...], m_sc, l_sc, acc_sc)
    o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                   ).astype(o_ref.dtype)


def _tree_body(lens_ref, q_ref, ck_ref, cv_ref, tk_ref, tv_ref, tm_ref,
               o_ref, m_sc, l_sc, acc_sc, *, bk: int, scale: float,
               n_kb: int, T: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    cache_len = lens_ref[b]

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_sc, l_sc, acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (T, D)

    @pl.when(jnp.logical_and(ki < n_kb, ki * bk < cache_len))
    def _cache_step():
        k = ck_ref[0, 0].astype(jnp.float32)                 # (bk, D)
        v = cv_ref[0, 0].astype(jnp.float32)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (T, bk), 1)
        _softmax_update(q, k, v, k_pos < cache_len, m_sc, l_sc, acc_sc)

    @pl.when(ki == n_kb)
    def _tree_step():
        _tree_finish(q, tk_ref, tv_ref, tm_ref, o_ref, m_sc, l_sc, acc_sc)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def legacy_tree_attention(q, cache_k, cache_v, tree_k, tree_v, tree_mask,
                          cache_len, *, bk: int = 512,
                          interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    B, Hq, T, D = q.shape
    Hkv, S = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    bk = min(bk, S)
    assert S % bk == 0
    n_kb = S // bk
    scale = 1.0 / (D ** 0.5)

    body = functools.partial(_tree_body, bk=bk, scale=scale, n_kb=n_kb, T=T)
    grid = (B, Hq, n_kb + 1)
    clamp = lambda j: jnp.minimum(j, n_kb - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, T, D), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j, lens: (b, h // G, clamp(j), 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j, lens: (b, h // G, clamp(j), 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, j, lens: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, j, lens: (b, h // G, 0, 0)),
            pl.BlockSpec((T, T), lambda b, h, j, lens: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, T, D), lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q, cache_k, cache_v, tree_k, tree_v, tree_mask)


def _tree_paged_body(lens_ref, table_ref, q_ref, pk_ref, pv_ref, tk_ref,
                     tv_ref, tm_ref, o_ref, m_sc, l_sc, acc_sc, *, bs: int,
                     scale: float, M: int, T: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    cache_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_sc, l_sc, acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (T, D)

    entry = table_ref[b, jnp.minimum(j, M - 1)]
    in_cache = jnp.logical_and(j < M, j * bs < cache_len)

    @pl.when(jnp.logical_and(in_cache, entry != NULL_BLOCK))
    def _cache_step():
        k = pk_ref[0, :, 0].astype(jnp.float32)              # (bs, D)
        v = pv_ref[0, :, 0].astype(jnp.float32)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (T, bs), 1)
        _softmax_update(q, k, v, k_pos < cache_len, m_sc, l_sc, acc_sc)

    @pl.when(j == M)
    def _tree_step():
        _tree_finish(q, tk_ref, tv_ref, tm_ref, o_ref, m_sc, l_sc, acc_sc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def legacy_tree_attention_paged(q, pool_k, pool_v, tree_k, tree_v, tree_mask,
                                cache_len, block_table, *,
                                interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    B, Hq, T, D = q.shape
    bs, Hkv = pool_k.shape[1], pool_k.shape[2]
    M = block_table.shape[1]
    G = Hq // Hkv
    assert bs % 8 == 0, f"pool block_size {bs} must be a multiple of 8"
    scale = 1.0 / (D ** 0.5)

    body = functools.partial(_tree_paged_body, bs=bs, scale=scale, M=M, T=T)
    grid = (B, Hq, M + 1)
    clamp = lambda j: jnp.minimum(j, M - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, j, lens, tbl: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, h, j, lens, tbl:
                         (tbl[b, clamp(j)], 0, h // G, 0)),
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, h, j, lens, tbl:
                         (tbl[b, clamp(j)], 0, h // G, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, j, lens, tbl: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, j, lens, tbl: (b, h // G, 0, 0)),
            pl.BlockSpec((T, T), lambda b, h, j, lens, tbl: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, T, D),
                               lambda b, h, j, lens, tbl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, block_table, q, pool_k, pool_v, tree_k, tree_v, tree_mask)
