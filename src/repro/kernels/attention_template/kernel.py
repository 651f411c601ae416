"""One parameterized Pallas online-softmax attention template (DESIGN.md §11).

Every attention kernel in the repo is an instantiation of the two bodies
in this file, specialized at trace time by a static :class:`TemplateSpec`:

* ``kind="self"`` — the flash/prefill family: S queries attend to the
  same S keys (causal, optional static sliding window).  Grid
  ``(B, Hq, S/bq, S/bk)``, kv axis innermost and sequential.
* ``kind="tree"`` — the verify/decode family: T tree tokens attend to a
  ragged KV cache plus themselves under an ancestor mask.  Grid
  ``(B, Hq, n_cache_steps + 1)``; the final step folds in the tree block.

Orthogonal axes of the spec:

* ``layout`` — the cache adapter.  ``"dense"`` walks per-slot
  ``(B, Hkv, S, D)`` strips in ``bk``-sized tiles; ``"paged"`` walks a
  head-major global pool ``(num_blocks, Hkv, block_size, D)`` through a
  scalar-prefetched ``block_table[b, j]`` (NULL entries and entries past
  ``cache_len`` are compute-skipped — ragged early-exit).  Both layouts
  put the token axis second-to-last, so every cache tile is a
  ``(bk, D)`` slab that the TPU's (sublane, lane) tiling accepts.
* ``windowed`` — the sliding-window mask-mod hook: a TRACED window (one
  int32, scalar-prefetched, so one compiled kernel serves a scan group
  mixing local and global layers) plus absolute query positions
  ``q_pos``.  ``window <= 0`` at runtime is an exact no-op of the mask.
  Precondition (asserted by construction in the verify path): every real
  query row sits at ``q_pos >= cache_len`` — that is what lets the
  window hook skip cache blocks entirely behind the furthest-back reach
  ``cache_len - window`` without knowing per-row positions.
* ``mla`` — the absorbed-latent scoring hook (DeepSeek MLA): the cache
  carries two streams, a rank-``r`` latent and a rank-``rd`` decoupled
  RoPE key.  K tiles are ``[latent ‖ rope]`` concatenated in-register;
  the VALUE tile is the latent itself, so the output is ``o_lat``
  (B, Hq, T, r) which the caller un-absorbs through ``w_uv``.

All instantiations share ``_softmax_update`` verbatim — the parity tests
assert bit-compatibility across layouts, and the pre-refactor kernels are
frozen in ``tests/_legacy_kernels.py`` as bit-identity oracles.

Block sizes are static template parameters; their per-backend defaults
come from the committed autotuner winner cache via
``repro.kernels.tuned_block_sizes`` (see ``repro.kernels.autotune``).
Requested sizes that don't tile the sequence are legalized by
``pad-or-clamp`` (never an assert): clamp to a >=8 divisor when one
exists, otherwise pad the operands and mask the tail.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
NULL_BLOCK = 0   # physical pool block 0 is reserved; never read unmasked


class TemplateSpec(NamedTuple):
    """Static parameterization of the attention template (hashable: it is
    a jit static argument and part of the trace cache key)."""

    kind: str = "tree"        # "self" (flash/prefill) | "tree" (verify)
    layout: str = "dense"     # "dense" | "paged"
    mla: bool = False         # absorbed-latent scoring (K=[lat‖rope], V=lat)
    windowed: bool = False    # traced sliding window + q_pos operands


# ---------------------------------------------------------------------------
# shared online-softmax core
# ---------------------------------------------------------------------------


def _init_scratch(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc):
    """One online-softmax accumulation of (k, v) under ``mask`` — shared
    verbatim by every template instantiation so their numerics can never
    desynchronize (the parity tests assert bit-compatibility)."""
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


# ---------------------------------------------------------------------------
# block-size legalization (pad-or-clamp; ValueError only when impossible)
# ---------------------------------------------------------------------------


def _divisor_at_most(n: int, b: int) -> int:
    for c in range(min(b, n), 0, -1):
        if n % c == 0:
            return c
    return 1


def _legalize_tree_bk(S: int, bk: int) -> tuple[int, int]:
    """Return (bk, padded_S) for a dense tree cache of length S.  Clamp to
    a >=8 divisor of S when one exists; otherwise keep the requested bk
    and report the padded extent (the pad is masked by cache_len)."""
    if S <= 0:
        raise ValueError(f"cache length must be positive, got S={S}")
    if bk <= 0:
        raise ValueError(f"block size must be positive, got bk={bk}")
    bk = min(bk, S)
    if S % bk == 0:
        return bk, S
    d = _divisor_at_most(S, bk)
    if d >= 8:
        return d, S
    return bk, -(-S // bk) * bk


def _legalize_self_blocks(S: int, bq: int, bk: int) -> tuple[int, int, int]:
    """Return (bq, bk, padded_S) for the self-attention family, where the
    SAME padded extent must tile both the query and key axes."""
    if S <= 0:
        raise ValueError(f"sequence length must be positive, got S={S}")
    if bq <= 0 or bk <= 0:
        raise ValueError(f"block sizes must be positive, got ({bq}, {bk})")
    bq, bk = min(bq, S), min(bk, S)
    if S % bq == 0 and S % bk == 0:
        return bq, bk, S
    dq, dk = _divisor_at_most(S, bq), _divisor_at_most(S, bk)
    if min(dq, dk) >= 8:
        return dq, dk, S
    step = math.lcm(bq, bk)
    return bq, bk, -(-S // step) * step


# ---------------------------------------------------------------------------
# "self" family (flash/prefill): S x S, causal + optional static window
# ---------------------------------------------------------------------------


def _self_body(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               bq: int, bk: int, scale: float, window: int, causal: bool,
               n_kb: int, s_real: Optional[int]):
    # Op-for-op the pre-refactor flash body (bit-identity oracle:
    # tests/_legacy_kernels.py); ``s_real`` adds a static tail mask only
    # when legalization padded S.
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_sc, l_sc, acc_sc)

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
    if s_real is not None:
        mask &= k_pos < s_real
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
def self_attention(q, k, v, *, causal: bool = True, window: int = 0,
                   bq: int = 128, bk: int = 128,
                   interpret: bool | None = None):
    """Template instantiation, self family.  q: (B,Hq,S,D); k/v:
    (B,Hkv,S,D); GQA folded via the head index map.  Returns (B,Hq,S,D).
    interpret: None => auto (compile on TPU, interpret elsewhere)."""
    interpret = resolve_interpret(interpret)
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq, bk, Sp = _legalize_self_blocks(S, bq, bk)
    if Sp != S:
        pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    n_qb, n_kb = Sp // bq, Sp // bk
    scale = 1.0 / (D ** 0.5)

    grid = (B, Hq, n_qb, n_kb)
    body = functools.partial(_self_body, bq=bq, bk=bk, scale=scale,
                             window=window, causal=causal, n_kb=n_kb,
                             s_real=None if Sp == S else S)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sp, D), q.dtype),
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
    return out if Sp == S else out[:, :, :S]


# ---------------------------------------------------------------------------
# "tree" family (verify/decode): ragged cache sweep + final tree step
# ---------------------------------------------------------------------------


def _tree_template_body(spec: TemplateSpec, *refs, bk: int, scale: float,
                        n_steps: int, T: int):
    paged = spec.layout == "paged"
    it = iter(refs)
    lens_ref = next(it)
    table_ref = next(it) if paged else None
    win_ref = next(it) if spec.windowed else None
    q_ref = next(it)
    k_ref = next(it)
    k2_ref = next(it) if spec.mla else None
    v_ref = None if spec.mla else next(it)
    tk_ref = next(it)
    tk2_ref = next(it) if spec.mla else None
    tv_ref = None if spec.mla else next(it)
    tm_ref = next(it)
    qpos_ref = next(it) if spec.windowed else None
    o_ref = next(it)
    m_sc, l_sc, acc_sc = next(it), next(it), next(it)

    b = pl.program_id(0)
    j = pl.program_id(2)
    cache_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_sc, l_sc, acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (T, Dk)

    if spec.windowed:
        w = win_ref[0]
        q_abs = qpos_ref[0, 0]                               # (T,) int32

    in_cache = jnp.logical_and(j < n_steps, j * bk < cache_len)
    if paged:
        entry = table_ref[b, jnp.minimum(j, n_steps - 1)]
        in_cache = jnp.logical_and(in_cache, entry != NULL_BLOCK)
    if spec.windowed:
        # Every real query row has q_pos >= cache_len (verify positions
        # are cache_len + depth), so a cache block whose last position
        # sits at or behind cache_len - w is invisible to ALL rows.
        # (written without a boolean select: Mosaic cannot lower one)
        reachable = (j + 1) * bk - 1 > cache_len - w
        in_cache = jnp.logical_and(in_cache,
                                   jnp.logical_or(w <= 0, reachable))

    def _load(ref):
        # dense strips and pool blocks are both (1, 1, bk, D) tiles
        return ref[0, 0].astype(jnp.float32)

    @pl.when(in_cache)
    def _cache_step():
        if spec.mla:
            k_lat = _load(k_ref)                             # (bk, r)
            k = jnp.concatenate([k_lat, _load(k2_ref)], axis=-1)
            v = k_lat
        else:
            k = _load(k_ref)                                 # (bk, D)
            v = _load(v_ref)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (T, bk), 1)
        mask = k_pos < cache_len
        if spec.windowed:
            mask = jnp.logical_and(
                mask, jnp.logical_or(w <= 0, q_abs[:, None] - k_pos < w))
        _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc)

    @pl.when(j == n_steps)
    def _tree_step():
        if spec.mla:
            tk_lat = tk_ref[0, 0].astype(jnp.float32)        # (T, r)
            k = jnp.concatenate(
                [tk_lat, tk2_ref[0, 0].astype(jnp.float32)], axis=-1)
            v = tk_lat
        else:
            k = tk_ref[0, 0].astype(jnp.float32)             # (T, D)
            v = tv_ref[0, 0].astype(jnp.float32)
        mask = tm_ref[...]
        if spec.windowed:
            # tree token j sits at absolute position cache_len + j
            kv_pos = cache_len + jax.lax.broadcasted_iota(
                jnp.int32, (T, T), 1)
            mask = jnp.logical_and(
                mask, jnp.logical_or(w <= 0, q_abs[:, None] - kv_pos < w))
        _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc)
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "bk", "scale",
                                             "interpret"))
def tree_attention_template(q, cache_k, cache_v, tree_k, tree_v, tree_mask,
                            cache_len, block_table=None, window=None,
                            q_pos=None, cache_k2=None, tree_k2=None, *,
                            spec: TemplateSpec = TemplateSpec(),
                            bk: int | None = None,
                            scale: float | None = None,
                            interpret: bool | None = None):
    """Template instantiation, tree family (kernel layout).

    q: (B,Hq,T,Dk).  Non-MLA: cache_k/v are the dense per-slot cache
    (B,Hkv,S,D) or the head-major global pool (num_blocks, Hkv,
    block_size, D); tree_k/v: (B,Hkv,T,D).  MLA (``spec.mla``):
    cache_k/cache_k2 carry the latent (rank r) and RoPE (rank rd)
    streams with Hkv == 1,
    ``cache_v``/``tree_v`` must be None, and the result is o_lat
    (B,Hq,T,r).  Paged (``spec.layout == 'paged'``): ``block_table``
    (B, M) int32 required; the kv tile IS the allocator's block_size.
    Windowed (``spec.windowed``): ``window`` (traced int32 scalar, <= 0
    disables) and ``q_pos`` (B, T) int32 required.

    Returns (B, Hq, T, Dv) where Dv = Dk (non-MLA) or r (MLA).
    """
    interpret = resolve_interpret(interpret)
    paged = spec.layout == "paged"
    B, Hq, T, Dk = q.shape
    if spec.mla:
        if cache_v is not None or tree_v is not None:
            raise ValueError("MLA template: V rides the latent stream; "
                             "cache_v/tree_v must be None")
        r = cache_k.shape[-1]
        rd = cache_k2.shape[-1]
        if r + rd != Dk:
            raise ValueError(f"MLA q dim {Dk} != latent {r} + rope {rd}")
        dims = (r, rd)           # K streams; V is the latent (Dv = r)
        Dv = r
    else:
        dims = (Dk,)
        Dv = Dk
    Hkv = cache_k.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (Dk ** 0.5)

    pads = []
    if paged:
        if block_table is None:
            raise ValueError("paged template requires a block_table")
        bs = cache_k.shape[2]
        if bs % 8 != 0:
            # the allocator's block_size IS the kv tile's sublane extent:
            # 8 is the f32 tiling floor
            raise ValueError(
                f"pool block_size {bs} must be a multiple of 8")
        bk = bs
        n_steps = block_table.shape[1]
    else:
        S = cache_k.shape[2]
        bk, Sp = _legalize_tree_bk(S, 512 if bk is None else bk)
        if Sp != S:
            # zero-pad the cache tail; cache_len <= S masks it exactly
            pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
            cache_k = jnp.pad(cache_k, pad)
            if spec.mla:
                cache_k2 = jnp.pad(cache_k2, pad)
            else:
                cache_v = jnp.pad(cache_v, pad)
        n_steps = (Sp if Sp != S else S) // bk

    clamp = lambda j: jnp.minimum(j, n_steps - 1)
    n_pf = 1 + (1 if paged else 0) + (1 if spec.windowed else 0)

    # prefetch operands: (cache_len, [block_table], [window])
    prefetch = [cache_len.astype(jnp.int32)]
    if paged:
        prefetch.append(block_table)
    if spec.windowed:
        if window is None or q_pos is None:
            raise ValueError("windowed template requires window and q_pos")
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))

    # tensor operands + matching in_specs, in body parse order
    operands = [q]
    in_specs = [pl.BlockSpec((1, 1, T, Dk),
                             lambda b, h, j, *pf: (b, h, 0, 0))]
    if paged:
        def kv_map(b, h, j, *pf):
            return (pf[1][b, clamp(j)], h // G, 0, 0)
    else:
        def kv_map(b, h, j, *pf):
            return (b, h // G, clamp(j), 0)
    kv_block = lambda d: (1, 1, bk, d)
    tree_map = lambda b, h, j, *pf: (b, h // G, 0, 0)

    cache_streams = ((cache_k, cache_k2) if spec.mla
                     else (cache_k, cache_v))
    for arr, d in zip(cache_streams, dims * 2 if not spec.mla else dims):
        operands.append(arr)
        in_specs.append(pl.BlockSpec(kv_block(d), kv_map))
    tree_streams = ((tree_k, tree_k2) if spec.mla else (tree_k, tree_v))
    for arr, d in zip(tree_streams, dims * 2 if not spec.mla else dims):
        operands.append(arr)
        in_specs.append(pl.BlockSpec((1, 1, T, d), tree_map))
    operands.append(tree_mask)
    in_specs.append(pl.BlockSpec((T, T), lambda b, h, j, *pf: (0, 0)))
    if spec.windowed:
        # (B, 1, T): a (1, 1, T) block's last two dims equal the array's
        operands.append(q_pos.astype(jnp.int32)[:, None, :])
        in_specs.append(pl.BlockSpec((1, 1, T),
                                     lambda b, h, j, *pf: (b, 0, 0)))

    body = functools.partial(_tree_template_body, spec, bk=bk, scale=scale,
                             n_steps=n_steps, T=T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pf,
        grid=(B, Hq, n_steps + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, T, Dv),
                               lambda b, h, j, *pf: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)
