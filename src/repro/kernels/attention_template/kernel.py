"""One parameterized Pallas online-softmax attention template (DESIGN.md §11).

Every attention kernel in the repo is an instantiation of the two bodies
in this file, specialized at trace time by a static :class:`TemplateSpec`:

* ``kind="self"`` — the flash/prefill family: S queries attend to the
  same S keys (causal, optional static sliding window).  Grid
  ``(B, Hq, S/bq, S/bk)``, kv axis innermost and sequential.
* ``kind="tree"`` — the verify/decode family: T tree tokens attend to a
  ragged KV cache plus themselves under an ancestor mask, the tree folded
  in after the cache.

Orthogonal axes of the spec:

* ``layout`` — the cache adapter.  ``"dense"`` walks per-slot
  ``(B, Hkv, S, D)`` strips in ``bk``-sized tiles on the grid
  ``(B, Hq, n_steps + 1)``.  ``"paged"`` runs one program per row, grid
  ``(B,)``: the G query heads of each KV head are one ``(G*T, D)`` query
  tile, and the body walks only the row's live pages of a head-major
  global pool ``(num_blocks, Hkv, block_size, D)`` — a loop of
  ``ceil(cache_len / block_size)`` pages from the scalar-prefetched
  ``cache_len[b]``, ``ppb`` pages to a compute block, each page (all KV
  heads) DMA'd by hand through the scalar-prefetched
  ``block_table[b, p]`` into a double-buffered VMEM tile.  NULL entries
  are never copied and are masked; pages past ``cache_len`` cost
  nothing.  Paged MLA keeps the per-entry grid ``(B, Hq, M + 1)``
  (``_walks_pages``).  Both layouts put the token axis second-to-last,
  so every cache tile is a ``(tokens, D)`` slab that the TPU's
  (sublane, lane) tiling accepts.
* ``windowed`` — the sliding-window mask-mod hook: a TRACED window (one
  int32, scalar-prefetched, so one compiled kernel serves a scan group
  mixing local and global layers) plus absolute query positions
  ``q_pos``.  ``window <= 0`` at runtime is an exact no-op of the mask.
  Precondition (asserted by construction in the verify path): every real
  query row sits at ``q_pos >= cache_len`` — that is what lets the
  window hook skip cache blocks entirely behind the furthest-back reach
  ``cache_len - window`` without knowing per-row positions (the paged
  walk starts at the page of ``cache_len - window + 1``).
* ``mla`` — the absorbed-latent scoring hook (DeepSeek MLA): the cache
  carries two streams, a rank-``r`` latent and a rank-``rd`` decoupled
  RoPE key.  K tiles are ``[latent ‖ rope]`` concatenated in-register;
  the VALUE tile is the latent itself, so the output is ``o_lat``
  (B, Hq, T, r) which the caller un-absorbs through ``w_uv``.

All instantiations share ``_softmax_update`` verbatim — the parity tests
assert bit-compatibility across layouts, and the pre-refactor kernels are
frozen in ``tests/_legacy_kernels.py`` as bit-identity oracles (the paged
walk matches its frozen kernel at ``ppb = 1``, one page per update).

Block sizes (``bk``, the paged ``ppb``) are static template parameters;
their per-backend defaults come from the committed autotuner winner
cache via ``repro.kernels.tuned_block_sizes`` (see
``repro.kernels.autotune``).
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


# tokens in one compute block of the paged sweep, the default ppb's target
PAGED_BLOCK_TOKENS = 128


def paged_defaults(block_size: int) -> dict:
    """Built-in block sizes of the paged tree variants: the tree axis'
    ``pad_to`` and the pages per compute block ``ppb`` (about
    ``PAGED_BLOCK_TOKENS`` tokens, so 8 pages of 16)."""
    return {"pad_to": 8, "ppb": max(1, PAGED_BLOCK_TOKENS // block_size)}


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


def _kv_tile(spec: TemplateSpec, k, k2_or_v):
    """f32 (K, V) of one tile.  MLA: K is ``[latent ‖ rope]`` concatenated
    in-register and V is the latent itself."""
    k = k.astype(jnp.float32)
    x = k2_or_v.astype(jnp.float32)
    if spec.mla:
        return jnp.concatenate([k, x], axis=-1), k
    return k, x


def _window_mask(mask, w, q_abs, kv_pos):
    # (written without a boolean select: Mosaic cannot lower one)
    return jnp.logical_and(
        mask, jnp.logical_or(w <= 0, q_abs[:, None] - kv_pos < w))


def _tree_step(spec, q, tk, tk2_or_v, mask, cache_len, w, q_abs, m_sc, l_sc,
               acc_sc):
    """Fold in the tree's own T tokens under the static ancestor mask."""
    k, v = _kv_tile(spec, tk, tk2_or_v)
    if spec.windowed:
        # tree token j sits at absolute position cache_len + j
        kv_pos = cache_len + jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
        mask = _window_mask(mask, w, q_abs, kv_pos)
    _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc)
    return acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


def _walks_pages(spec: TemplateSpec) -> bool:
    """Whether the paged sweep walks each row's live pages in-body.

    Absorbed MLA stays on the per-entry grid: its RoPE stream is narrower
    than the 128 lanes of a tile, and Mosaic refuses to slice a page out
    of such a pool in HBM for a manual copy.
    """
    return spec.layout == "paged" and not spec.mla


def _tree_grid_body(spec: TemplateSpec, *refs, bk: int, scale: float,
                    n_steps: int, T: int):
    """Grid ``(B, Hq, n_steps + 1)``: one cache tile of ``bk`` tokens per
    step (a dense strip, or the pool block of table entry ``j``), the tree
    folded in at the last step."""
    paged = spec.layout == "paged"
    it = iter(refs)
    lens_ref = next(it)
    table_ref = next(it) if paged else None
    win_ref = next(it) if spec.windowed else None
    q_ref = next(it)
    k_ref, k2v_ref = next(it), next(it)
    tk_ref, tk2v_ref = next(it), next(it)
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
    w = win_ref[0] if spec.windowed else None
    q_abs = qpos_ref[0, 0] if spec.windowed else None        # (T,) int32

    in_cache = jnp.logical_and(j < n_steps, j * bk < cache_len)
    if paged:
        entry = table_ref[b, jnp.minimum(j, n_steps - 1)]
        in_cache = jnp.logical_and(in_cache, entry != NULL_BLOCK)
    if spec.windowed:
        # Every real query row has q_pos >= cache_len (verify positions
        # are cache_len + depth), so a cache block whose last position
        # sits at or behind cache_len - w is invisible to ALL rows.
        reachable = (j + 1) * bk - 1 > cache_len - w
        in_cache = jnp.logical_and(in_cache,
                                   jnp.logical_or(w <= 0, reachable))

    @pl.when(in_cache)
    def _cache_step():
        # dense strips and pool blocks are both (1, 1, bk, d) tiles
        k, v = _kv_tile(spec, k_ref[0, 0], k2v_ref[0, 0])
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (T, bk), 1)
        mask = k_pos < cache_len
        if spec.windowed:
            mask = _window_mask(mask, w, q_abs, k_pos)
        _softmax_update(q, k, v, mask, m_sc, l_sc, acc_sc)

    @pl.when(j == n_steps)
    def _finish():
        o = _tree_step(spec, q, tk_ref[0, 0], tk2v_ref[0, 0], tm_ref[...],
                       cache_len, w, q_abs, m_sc, l_sc, acc_sc)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _tree_paged_body(spec: TemplateSpec, *refs, bs: int, ppb: int,
                     scale: float, M: int, T: int, G: int, Hkv: int):
    """Paged layout: one program per row, grid ``(B,)``.

    The G query heads of each KV head are one ``(G*T, Dk)`` query tile.
    The body walks only the row's live pages: a loop over compute blocks
    of ``ppb`` consecutive table entries, its trip count from
    ``cache_len[b]``.  Each page, all KV heads at once, is DMA'd from the
    pool in HBM into a double-buffered ``(Hkv, ppb*bs, d)`` VMEM tile
    through ``block_table[b, p]`` while the previous block computes.  The
    loops over pages and heads are unrolled (``unroll=True``), so the KV
    heads' independent updates overlap.  NULL entries are never copied
    and are masked; pages past ``cache_len`` cost nothing.  At ``ppb == 1``
    each head's updates run page by page in table order, the legacy
    kernel's order.
    """
    it = iter(refs)
    lens_ref, table_ref = next(it), next(it)
    win_ref = next(it) if spec.windowed else None
    q_ref = next(it)
    pools = (next(it), next(it))          # HBM: (K, V) or (latent, rope)
    tk_ref, tk2v_ref = next(it), next(it)
    tm_ref = next(it)
    qpos_ref = next(it) if spec.windowed else None
    o_ref = next(it)
    bufs = (next(it), next(it))           # VMEM (2, Hkv, ppb*bs, d) each
    sems = next(it)                       # DMA (2 streams, 2 slots)
    q_sc, m_sc, l_sc, acc_sc = next(it), next(it), next(it), next(it)

    b = pl.program_id(0)
    cache_len = lens_ref[b]
    GT, W = G * T, ppb * bs

    _init_scratch(m_sc, l_sc, acc_sc)
    q = q_ref[0].astype(jnp.float32) * scale                 # (Hq, T, Dk)
    q_sc[...] = q.reshape(Hkv, GT, q.shape[-1])

    n_pages = jnp.minimum((cache_len + bs - 1) // bs, M)
    first = 0
    w = q_abs = None
    if spec.windowed:
        w = win_ref[0]
        q_abs = qpos_ref[0, 0]                                # (G*T,) int32
        # Every real query row has q_pos >= cache_len (verify positions
        # are cache_len + depth), so no row sees position cache_len - w or
        # behind it: the walk starts at the page of cache_len - w + 1.
        first = (jnp.maximum(cache_len - w + 1, 0) // bs
                 * (w > 0).astype(jnp.int32))
    n_blocks = (jnp.maximum(n_pages - first, 0) + ppb - 1) // ppb

    def page(c, i):
        """Table entry of the i-th page of block c, and whether it is live."""
        p = first + c * ppb + i
        entry = table_ref[b, jnp.minimum(p, M - 1)]
        return entry, jnp.logical_and(p < n_pages, entry != NULL_BLOCK)

    def copy_block(c, slot, wait: bool):
        def copy_page(i, carry):
            entry, live = page(c, i)

            @pl.when(live)
            def _copy():
                for s in range(2):
                    cp = pltpu.make_async_copy(
                        pools[s].at[entry],
                        bufs[s].at[slot, :, pl.ds(pl.multiple_of(i * bs, bs),
                                                  bs)],
                        sems.at[s, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()
            return carry
        jax.lax.fori_loop(0, ppb, copy_page, 0, unroll=True)

    @pl.when(n_blocks > 0)
    def _prologue():
        copy_block(0, 0, wait=False)

    def block(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_blocks)
        def _prefetch():
            copy_block(c + 1, 1 - slot, wait=False)

        copy_block(c, slot, wait=True)
        k0 = (first + c * ppb) * bs
        # Pages that were not copied (NULL, or past cache_len) hold
        # whatever VMEM held: mask their columns, and zero their V rows so
        # that masked columns add exactly nothing.
        col = jax.lax.broadcasted_iota(jnp.int32, (GT, W), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)

        def page_live(i, lives):
            live = page(c, i)[1].astype(jnp.int32)
            in_col = jnp.logical_and(col >= i * bs, col < (i + 1) * bs)
            in_row = jnp.logical_and(row >= i * bs, row < (i + 1) * bs)
            return (jnp.where(in_col, live, lives[0]),
                    jnp.where(in_row, live, lives[1]))
        live_col, live_row = jax.lax.fori_loop(
            0, ppb, page_live, (jnp.zeros((GT, W), jnp.int32),
                                jnp.zeros((W, 1), jnp.int32)), unroll=True)
        mask = jnp.logical_and(k0 + col < cache_len, live_col > 0)
        row_ok = jnp.logical_and(k0 + row < cache_len, live_row > 0)
        if spec.windowed:
            mask = _window_mask(mask, w, q_abs, k0 + col)

        def head(h, carry):
            k, v = _kv_tile(spec, bufs[0][slot, h], bufs[1][slot, h])
            v = jnp.where(row_ok, v, 0.0)
            _softmax_update(q_sc[h], k, v, mask, m_sc.at[h], l_sc.at[h],
                            acc_sc.at[h])
            return carry
        jax.lax.fori_loop(0, Hkv, head, 0, unroll=True)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    tm = tm_ref[...].astype(jnp.int32)                        # (T, T)
    tm = jnp.concatenate([tm] * G, axis=0) != 0               # (G*T, T)

    def tree(h, carry):
        o = _tree_step(spec, q_sc[h], tk_ref[0, h], tk2v_ref[0, h], tm,
                       cache_len, w, q_abs, m_sc.at[h], l_sc.at[h],
                       acc_sc.at[h])
        o_ref[0, pl.ds(h * G, G)] = o.reshape(G, T, o.shape[-1]).astype(
            o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, Hkv, tree, 0, unroll=True)


@functools.partial(jax.jit, static_argnames=("spec", "bk", "ppb", "scale",
                                             "interpret"))
def tree_attention_template(q, cache_k, cache_v, tree_k, tree_v, tree_mask,
                            cache_len, block_table=None, window=None,
                            q_pos=None, cache_k2=None, tree_k2=None, *,
                            spec: TemplateSpec = TemplateSpec(),
                            bk: int | None = None,
                            ppb: int = 1,
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
    (B, M) int32 required; pages are the allocator's block_size and
    ``ppb`` of them make one compute block (``bk`` is the dense strip).
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
        cache_streams, tree_streams = (cache_k, cache_k2), (tree_k, tree_k2)
    else:
        dims = (Dk, Dk)
        Dv = Dk
        cache_streams, tree_streams = (cache_k, cache_v), (tree_k, tree_v)
    Hkv = cache_k.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (Dk ** 0.5)
    if spec.windowed and (window is None or q_pos is None):
        raise ValueError("windowed template requires window and q_pos")

    # prefetch operands: (cache_len, [block_table], [window])
    prefetch = [cache_len.astype(jnp.int32)]
    if paged:
        if block_table is None:
            raise ValueError("paged template requires a block_table")
        bs = cache_k.shape[2]
        if bs % 8 != 0:
            # the allocator's block_size is a page's sublane extent: 8 is
            # the f32 tiling floor
            raise ValueError(
                f"pool block_size {bs} must be a multiple of 8")
        prefetch.append(block_table)
    if spec.windowed:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))

    if _walks_pages(spec):
        M = block_table.shape[1]
        ppb = max(1, min(int(ppb), M))
        GT = G * T
        grid = (B,)
        operands = [q, *cache_streams, *tree_streams, tree_mask]
        in_specs = [
            pl.BlockSpec((1, Hq, T, Dk), lambda b, *pf: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            *(pl.BlockSpec((1, Hkv, T, d), lambda b, *pf: (b, 0, 0, 0))
              for d in dims),
            pl.BlockSpec((T, T), lambda b, *pf: (0, 0)),
        ]
        if spec.windowed:
            # (B, 1, G*T): query row g*T + t sits at q_pos[b, t]
            operands.append(
                jnp.tile(q_pos.astype(jnp.int32), (1, G))[:, None, :])
            in_specs.append(pl.BlockSpec((1, 1, GT),
                                         lambda b, *pf: (b, 0, 0)))
        out_spec = pl.BlockSpec((1, Hq, T, Dv), lambda b, *pf: (b, 0, 0, 0))
        scratch = [*(pltpu.VMEM((2, Hkv, ppb * bs, d), a.dtype)
                     for a, d in zip(cache_streams, dims)),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.VMEM((Hkv, GT, Dk), jnp.float32),
                   pltpu.VMEM((Hkv, GT, 1), jnp.float32),
                   pltpu.VMEM((Hkv, GT, 1), jnp.float32),
                   pltpu.VMEM((Hkv, GT, Dv), jnp.float32)]
        body = functools.partial(_tree_paged_body, spec, bs=bs, ppb=ppb,
                                 scale=scale, M=M, T=T, G=G, Hkv=Hkv)
        semantics = ("parallel",)
    else:
        if paged:
            # one grid step per table entry; the kv tile is the pool block
            bk = bs
            n_steps = block_table.shape[1]
        else:
            S = cache_k.shape[2]
            bk, Sp = _legalize_tree_bk(S, 512 if bk is None else bk)
            if Sp != S:
                # zero-pad the cache tail; cache_len <= S masks it exactly
                pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
                cache_streams = tuple(jnp.pad(a, pad) for a in cache_streams)
            n_steps = Sp // bk
        clamp = lambda j: jnp.minimum(j, n_steps - 1)
        if paged:
            kv_map = lambda b, h, j, *pf: (pf[1][b, clamp(j)], h // G, 0, 0)
        else:
            kv_map = lambda b, h, j, *pf: (b, h // G, clamp(j), 0)
        grid = (B, Hq, n_steps + 1)
        operands = [q, *cache_streams, *tree_streams, tree_mask]
        in_specs = [
            pl.BlockSpec((1, 1, T, Dk), lambda b, h, j, *pf: (b, h, 0, 0)),
            *(pl.BlockSpec((1, 1, bk, d), kv_map) for d in dims),
            *(pl.BlockSpec((1, 1, T, d),
                           lambda b, h, j, *pf: (b, h // G, 0, 0))
              for d in dims),
            pl.BlockSpec((T, T), lambda b, h, j, *pf: (0, 0)),
        ]
        if spec.windowed:
            # (B, 1, T): a (1, 1, T) block's last two dims equal the array's
            operands.append(q_pos.astype(jnp.int32)[:, None, :])
            in_specs.append(pl.BlockSpec((1, 1, T),
                                         lambda b, h, j, *pf: (b, 0, 0)))
        out_spec = pl.BlockSpec((1, 1, T, Dv),
                                lambda b, h, j, *pf: (b, h, 0, 0))
        scratch = [pltpu.VMEM((T, 1), jnp.float32),
                   pltpu.VMEM((T, 1), jnp.float32),
                   pltpu.VMEM((T, Dv), jnp.float32)]
        body = functools.partial(_tree_grid_body, spec, bk=bk, scale=scale,
                                 n_steps=n_steps, T=T)
        semantics = ("parallel", "parallel", "arbitrary")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(*prefetch, *operands)
