"""Paged KV cache: vLLM-style block tables over a global block pool.

The dense engine reserves one ``max_len`` KV stripe per slot, so HBM —
not compute — caps ``max_batch``: a slot pays worst-case memory whether
its request uses it or not.  Paging replaces the per-slot stripes with

  * a global **block pool** per attention cache array:
    ``(L, num_blocks, Hkv, block_size, D)`` instead of the dense
    ``(L, B, max_len, Hkv, D)`` (MLA's headless latents: ``(L,
    num_blocks, block_size, r)``) — persistent HBM is ``num_blocks ×
    block_size`` tokens, which may be far smaller than ``max_batch ×
    max_len`` (oversubscription).  The pool is head-major so that one
    (block, head) slab ``(block_size, D)`` is a tile the TPU kernels can
    DMA: the token axis sits second-to-last in every pool array;
  * a per-slot **block table** ``(B, blocks_per_slot)`` mapping logical
    token-block j of the slot to a physical pool block.  A slot only owns
    blocks for tokens it has actually committed plus the speculative
    scratch region ``[len, len + T)`` (see DESIGN.md §6).

Physical block 0 is the reserved **NULL block**: every unallocated table
entry points at it.  It accumulates garbage writes (inactive rows'
scratch) and is never read at an unmasked position — the verify mask only
admits positions ``< cache_len`` or inside the tree scratch
``[len, len + T)``, both of which the allocator keeps covered by real,
slot-owned blocks, and the native kernel additionally compute-skips any
NULL table entry outright.

**Steady-state execution is native** (``attention="native"``, the
default): ``paged_spec_decode_step`` hands the pools and the block table
straight to ``spec_decode_step``, whose verify forward streams K/V blocks
from the pool with the ``tree_attention_paged`` Pallas kernel and whose
commit compacts accepted entries through the table
(``serving/cache.py``).  The step's transient footprint is O(B·T) scratch
writes plus the blocks actually streamed — never the dense
``(L, B, M·bs, ...)`` view.

The **gather/scatter shim** (``gather_view`` assembles the dense per-slot
view, the unmodified dense step runs on it, ``scatter_view`` writes it
back) survives in two roles only: the parity oracle for tests/benchmarks
(``attention="shim"``), and the per-slot strip that ``paged_join_slot``
gathers for prefill — join is per-request and off the steady-state path.

Only attention-shaped caches are paged: the ``'k'``/``'v'`` keys of
attn/shared-attn/MLA groups and the Hydra++ PrefixAttention cache, i.e.
everything with a ``max_len`` sequence axis.  Recurrent-state groups
(mamba2 ``ssd_state``/``conv_win``, rwkv6 ``wkv_state``/``shift_*``) are
O(1) per slot — there is nothing to page — and stay dense per-slot arrays
inside ``PagedState.pools`` (the documented asymmetry, DESIGN.md §6.5).

The host-side ``BlockAllocator`` (heap-ordered free pool, O(log n) per
block, ascending-id handout) lives here too; the serving policy around it
— allocation on join, growth before every step, release on finish,
preemption-to-queue on exhaustion — is
``serving/engine.py::PagedSpeculativeEngine``.  Under the async serve
loop (DESIGN.md §7) every one of those decisions runs in the
pre-dispatch phase against host mirrors that are one step stale; the
engine compensates with a per-step staleness margin, and block recycling
across in-flight steps is safe by device program order (an old step's
writes into a freed block always execute before any later prefill or
commit that could make the block readable).
"""
from __future__ import annotations

import heapq
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.heads import init_prefix_cache, prefix_forward
from repro.core.speculative import (DecodeState, StepResult, _first_token,
                                    autoregressive_step, join_slot,
                                    spec_decode_step)
from repro.models.model import forward, init_cache
from repro.serving.cache import ATTN_KEYS

NULL_BLOCK = 0


# ---------------------------------------------------------------------------
# host-side block allocator
# ---------------------------------------------------------------------------


class BlockAllocator:
    """Allocator over the global block pool (host side, eager).

    Block ids are ``[1, num_blocks)`` — physical block 0 is the reserved
    NULL block and is never handed out.  ``alloc`` is all-or-nothing: a
    request for more blocks than are free returns ``None`` and changes
    nothing, which is what lets the engine turn exhaustion into queueing /
    preemption instead of a crash.

    The free pool is a min-heap mirrored by a membership set: ``free`` is
    O(log n) per block and raises ``ValueError`` on a double/foreign free
    (a real exception — the old bare ``assert`` vanished under ``-O``),
    and ``alloc`` hands out the lowest free ids first, which keeps block
    placement deterministic for the byte-match tests.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (one is the reserved NULL)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # ascending list == valid min-heap; heappop hands out 1, 2, ...
        self._free_heap: List[int] = list(range(1, num_blocks))
        self._allocated: set = set()
        self.peak_in_use = 0

    @property
    def usable_blocks(self) -> int:
        """Pool capacity excluding the NULL block."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free_heap)

    @property
    def blocks_in_use(self) -> int:
        return len(self._allocated)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to cover ``n_tokens`` logical cache positions."""
        return -(-int(n_tokens) // self.block_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free_heap):
            return None
        got = [heapq.heappop(self._free_heap) for _ in range(n)]
        self._allocated.update(got)
        self.peak_in_use = max(self.peak_in_use, len(self._allocated))
        return got

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"double/foreign free of block {b}")
            self._allocated.discard(b)
            heapq.heappush(self._free_heap, b)


# ---------------------------------------------------------------------------
# device-side pool state + gather/scatter shim (fallback / oracle only)
# ---------------------------------------------------------------------------


class PagedState(NamedTuple):
    """DecodeState with attention caches in pool layout.

    ``pools`` mirrors the ``DecodeState.cache`` group structure, but every
    attention key holds ``(L, num_blocks, [Hkv,] block_size, D)`` and
    every recurrent-state key keeps its dense per-slot ``(L, B, ...)``
    layout.
    The block table is NOT part of the state — the engine owns it host-side
    and passes it into each jitted step as a ``(B, M)`` int32 operand.
    """

    pools: Any
    prefix_k: Optional[jnp.ndarray]      # (num_blocks, Hkv, bs, hd) or None
    prefix_v: Optional[jnp.ndarray]
    cache_len: jnp.ndarray               # (B,)
    last_token: jnp.ndarray              # (B,)
    last_hidden: jnp.ndarray             # (B, d)
    rng: jnp.ndarray


def init_paged_state(params, draft_params, cfg: ModelConfig, max_batch: int,
                     num_blocks: int, block_size: int, rng) -> PagedState:
    """Empty paged pool: attention caches as block pools, recurrent-state
    groups dense per slot, every row idle."""
    # init_cache already knows every per-arch group layout: its shapes at
    # (batch=num_blocks, max_len=block_size) are the pool's up to the
    # head-major move, and at (batch=max_batch) it gives the per-slot
    # shape for recurrent-state keys (which carry no seq axis).
    attn_like = jax.eval_shape(lambda: init_cache(cfg, num_blocks,
                                                  block_size))
    state_like = init_cache(cfg, max_batch, 1)
    pools = []
    for ga, gs in zip(attn_like, state_like):
        pools.append({k: (_pool_zeros(ga[k], 2) if k in ATTN_KEYS
                          else gs[k]) for k in ga})
    pk = pv = None
    if draft_params is not None and "prefix" in draft_params:
        pc = jax.eval_shape(lambda: init_prefix_cache(cfg, num_blocks,
                                                      block_size))
        pk, pv = _pool_zeros(pc["k"], 1), _pool_zeros(pc["v"], 1)
    return PagedState(
        pools=pools, prefix_k=pk, prefix_v=pv,
        cache_len=jnp.zeros((max_batch,), jnp.int32),
        last_token=jnp.zeros((max_batch,), jnp.int32),
        last_hidden=jnp.zeros((max_batch, cfg.d_model), jnp.dtype(cfg.dtype)),
        rng=rng)


def _pool_zeros(like, seq_axis: int):
    """Zeros in pool layout for the dense-layout shape ``like``, whose
    token axis is ``seq_axis``: that axis moves to second-to-last, so GQA's
    (…, N, bs, Hkv, D) becomes (…, N, Hkv, bs, D) and MLA's (…, N, bs, r)
    stays as it is."""
    shape = list(like.shape)
    tok = shape.pop(seq_axis)
    shape.insert(len(shape) - 1, tok)
    return jnp.zeros(shape, like.dtype)


def _gather_attn(pool, table):
    """pool (L, N, [Hkv,] bs, D) + table (B, M) -> dense view
    (L, B, M*bs, [Hkv,] D)."""
    L, bs = pool.shape[0], pool.shape[-2]
    B, M = table.shape
    view = jnp.moveaxis(pool[:, table], -2, 3)   # (L, B, M, bs, [Hkv,] D)
    return view.reshape(L, B, M * bs, *view.shape[4:])


def _scatter_attn(pool, view, table):
    """Write a dense view back into its pool blocks.  Table entries that
    alias the NULL block receive nondeterministic garbage — by construction
    those regions are never read unmasked."""
    L, bs = pool.shape[0], pool.shape[-2]
    B, M = table.shape
    blocks = view.reshape(L, B, M, bs, *view.shape[3:])
    return pool.at[:, table].set(
        jnp.moveaxis(blocks, 3, -2).astype(pool.dtype))


def gather_view(pstate: PagedState, table) -> DecodeState:
    """Assemble the dense per-slot DecodeState view the DENSE step
    functions consume.  ``table``: (B, M) int32 physical block ids.

    Off the steady-state path since the native kernel landed: used only
    by the ``attention="shim"`` oracle and (per-slot) by join."""
    cache = [{k: (_gather_attn(a, table) if k in ATTN_KEYS else a)
              for k, a in g.items()} for g in pstate.pools]
    pk = pv = None
    if pstate.prefix_k is not None:
        pk = _gather_attn(pstate.prefix_k[None], table)[0]
        pv = _gather_attn(pstate.prefix_v[None], table)[0]
    return DecodeState(cache=cache, cache_len=pstate.cache_len,
                       last_token=pstate.last_token,
                       last_hidden=pstate.last_hidden,
                       prefix_k=pk, prefix_v=pv, rng=pstate.rng)


def scatter_view(pstate: PagedState, view: DecodeState, table) -> PagedState:
    """Persist a stepped view back into the pool (attention keys scatter
    through the table; recurrent-state keys pass through dense)."""
    pools = [{k: (_scatter_attn(gp[k], gv[k], table) if k in ATTN_KEYS
                  else gv[k])
              for k in gp} for gp, gv in zip(pstate.pools, view.cache)]
    pk, pv = pstate.prefix_k, pstate.prefix_v
    if pk is not None:
        pk = _scatter_attn(pk[None], view.prefix_k[None], table)[0]
        pv = _scatter_attn(pv[None], view.prefix_v[None], table)[0]
    return PagedState(pools=pools, prefix_k=pk, prefix_v=pv,
                      cache_len=view.cache_len, last_token=view.last_token,
                      last_hidden=view.last_hidden, rng=view.rng)


# ---------------------------------------------------------------------------
# paged step / join wrappers (jit these; shapes depend only on
# (max_batch, blocks_per_slot, tree) — never on the block-table contents)
# ---------------------------------------------------------------------------


def _pools_as_state(pstate: PagedState) -> DecodeState:
    """Zero-copy relabel: the pools ARE the step state in the native path
    (spec_decode_step reads the layout off the block table's presence)."""
    return DecodeState(cache=pstate.pools, cache_len=pstate.cache_len,
                       last_token=pstate.last_token,
                       last_hidden=pstate.last_hidden,
                       prefix_k=pstate.prefix_k, prefix_v=pstate.prefix_v,
                       rng=pstate.rng)


def _state_as_pools(state: DecodeState) -> PagedState:
    return PagedState(pools=state.cache, prefix_k=state.prefix_k,
                      prefix_v=state.prefix_v, cache_len=state.cache_len,
                      last_token=state.last_token,
                      last_hidden=state.last_hidden, rng=state.rng)


def paged_spec_decode_step(params, draft_params, cfg: ModelConfig, tree,
                           pstate: PagedState, table, *,
                           criterion: str = "greedy", temperature: float = 0.7,
                           epsilon: float = 0.15,
                           active: Optional[jnp.ndarray] = None,
                           attention: str = "native") -> StepResult:
    """One speculative step over the paged pools.

    ``attention="native"`` (default): the block table rides into
    ``spec_decode_step`` and the verify forward streams pool blocks with
    the ``tree_attention_paged`` kernel — no dense view is ever built.
    ``attention="shim"``: gather -> unmodified dense step -> scatter; kept
    as the parity oracle and for triage, NOT a serving path.
    """
    if attention == "shim":
        view = gather_view(pstate, table)
        res = spec_decode_step(params, draft_params, cfg, tree, view,
                               criterion=criterion, temperature=temperature,
                               epsilon=epsilon, active=active)
        return StepResult(scatter_view(pstate, res.state, table),
                          res.emitted, res.n_emitted)
    if attention != "native":
        raise ValueError(f"attention must be 'native' or 'shim': {attention}")
    res = spec_decode_step(params, draft_params, cfg, tree,
                           _pools_as_state(pstate), criterion=criterion,
                           temperature=temperature, epsilon=epsilon,
                           active=active, block_table=table)
    return StepResult(_state_as_pools(res.state), res.emitted, res.n_emitted)


def paged_autoregressive_step(params, cfg: ModelConfig, pstate: PagedState,
                              table, *, greedy: bool = True,
                              temperature: float = 1.0,
                              active: Optional[jnp.ndarray] = None,
                              attention: str = "native") -> StepResult:
    """T=1 baseline step over the paged pools (same dispatch as
    ``paged_spec_decode_step``)."""
    if attention == "shim":
        view = gather_view(pstate, table)
        res = autoregressive_step(params, cfg, view, greedy=greedy,
                                  temperature=temperature, active=active)
        return StepResult(scatter_view(pstate, res.state, table),
                          res.emitted, res.n_emitted)
    if attention != "native":
        raise ValueError(f"attention must be 'native' or 'shim': {attention}")
    res = autoregressive_step(params, cfg, _pools_as_state(pstate),
                              greedy=greedy, temperature=temperature,
                              active=active, block_table=table)
    return StepResult(_state_as_pools(res.state), res.emitted, res.n_emitted)


def paged_join_slot(params, draft_params, cfg: ModelConfig,
                    pstate: PagedState, prompt, real_len, slot, table_row, *,
                    greedy: bool = True) -> PagedState:
    """Prefill one request into row ``slot``, writing through the slot's
    (freshly allocated) block-table row.

    Only the joining slot's view is gathered — a (1, M*bs, ...) strip per
    cache array — so join cost is independent of ``max_batch``.  The
    engine must have pointed ``table_row`` at blocks covering
    ``[0, max(P, real_len + scratch))`` before calling: the padded prefill
    writes ``[0, P)`` and the next verify step writes scratch at
    ``[real_len, real_len + T)``.
    """
    t1 = table_row[None, :]                                   # (1, M)
    cache1 = [{k: (_gather_attn(a, t1) if k in ATTN_KEYS
                   else a[:, slot][:, None])
               for k, a in g.items()} for g in pstate.pools]
    pk = pv = None
    if pstate.prefix_k is not None:
        pk = _gather_attn(pstate.prefix_k[None], t1)[0]
        pv = _gather_attn(pstate.prefix_v[None], t1)[0]
    view1 = DecodeState(
        cache=cache1, cache_len=jnp.zeros((1,), jnp.int32),
        last_token=jnp.zeros((1,), jnp.int32),
        last_hidden=jnp.zeros((1, cfg.d_model), pstate.last_hidden.dtype),
        prefix_k=pk, prefix_v=pv, rng=pstate.rng)
    joined = join_slot(params, draft_params, cfg, view1, prompt, real_len,
                       jnp.int32(0), greedy=greedy)
    pools = [{k: (_scatter_attn(gp[k], gj[k], t1) if k in ATTN_KEYS
                  else gp[k].at[:, slot].set(gj[k][:, 0].astype(gp[k].dtype)))
              for k in gp} for gp, gj in zip(pstate.pools, joined.cache)]
    npk, npv = pstate.prefix_k, pstate.prefix_v
    if npk is not None:
        npk = _scatter_attn(npk[None], joined.prefix_k[None], t1)[0]
        npv = _scatter_attn(npv[None], joined.prefix_v[None], t1)[0]
    return PagedState(
        pools=pools, prefix_k=npk, prefix_v=npv,
        cache_len=pstate.cache_len.at[slot].set(joined.cache_len[0]),
        last_token=pstate.last_token.at[slot].set(joined.last_token[0]),
        last_hidden=pstate.last_hidden.at[slot].set(
            joined.last_hidden[0].astype(pstate.last_hidden.dtype)),
        rng=joined.rng)


def paged_join_slot_chunk(params, draft_params, cfg: ModelConfig,
                          pstate: PagedState, chunk, start, real_len, slot,
                          table_row, *, final: bool,
                          view_blocks: Optional[int] = None,
                          greedy: bool = True) -> PagedState:
    """One chunk of a resumable prefill over the paged pools (DESIGN.md
    §8) — the paged twin of ``core/speculative.py::join_slot_chunk``.

    Unlike ``paged_join_slot`` this NEVER assembles the per-slot dense
    strip: the chunk forward receives the pools plus the slot's (1, M)
    table row and writes the chunk K/V token-granularly through the table
    (``_paged_scatter``), so prefill becomes a native pool consumer and
    the engine can allocate blocks incrementally — one chunk's coverage
    at a time — instead of the whole prompt's at join.  Attention gathers
    one LAYER's logical view per scan step (the per-layer transient, same
    class as the windowed/MLA verify fallback).  Table entries beyond the
    allocated coverage point at the NULL block, which absorbs pad/scratch
    garbage writes; the engine only ever relies on positions it allocated
    blocks for.

    ``view_blocks`` (static) truncates the slot's table row to its first
    ``view_blocks`` entries — the paged twin of ``join_slot_chunk``'s
    ``view_len``: attention gathers/sweeps only that many blocks per
    layer, so per-chunk cost tracks the prefill cursor instead of the
    full M-block view.  The extent must cover ``start + C`` positions;
    a covering extent's masked tail is an exact no-op, so the bits don't
    depend on it.
    """
    C = chunk.shape[0]
    t1 = table_row[:view_blocks][None, :]                     # (1, Mv)
    pos = (start + jnp.arange(C))[None, :]
    start1 = jnp.reshape(start, (1,)).astype(jnp.int32)
    valid = jnp.clip(real_len - start, 0, C)
    # first chunk: zero the carried recurrent state — the dense per-slot
    # rows still hold the previous occupant's state (see join_slot_chunk;
    # pool-layout attention needs no reset, stale entries are masked)
    fresh = jnp.asarray(start) == 0

    def _row_state(a):
        row = a[:, slot][:, None]
        return jnp.where(fresh, jnp.zeros_like(row), row)

    cache = [{k: (a if k in ATTN_KEYS else _row_state(a))
              for k, a in g.items()} for g in pstate.pools]
    out = forward(params, cfg, chunk[None, :], pos, mode="full",
                  cache=cache, cache_len=start1,
                  valid_len=jnp.reshape(valid, (1,)), block_table=t1,
                  want_logits=False)

    # attention arrays came back as updated pools (scattered through the
    # table inside the forward); recurrent rows are written back per slot
    pools = [{k: (go[k] if k in ATTN_KEYS
                  else gp[k].at[:, slot].set(go[k][:, 0].astype(gp[k].dtype)))
              for k in gp} for gp, go in zip(pstate.pools, out.cache)]

    h_seq = out.hidden
    pk, pv = pstate.prefix_k, pstate.prefix_v
    ph = None
    if draft_params is not None and "prefix" in draft_params:
        ph, pk, pv = prefix_forward(
            draft_params, cfg, h_seq, pos, cache_k=pk, cache_v=pv,
            cache_len=start1, block_table=t1, prefill=True)

    if not final:
        return PagedState(
            pools=pools, prefix_k=pk, prefix_v=pv,
            cache_len=pstate.cache_len.at[slot].set(
                (start + C).astype(jnp.int32)),
            last_token=pstate.last_token, last_hidden=pstate.last_hidden,
            rng=pstate.rng)

    idx = jnp.clip(valid - 1, 0, C - 1)
    h_last = h_seq[0, idx]
    tok0, rng = _first_token(params, cfg, h_last, pstate.rng, greedy)
    h = ph[0, idx] if ph is not None else h_last
    return PagedState(
        pools=pools, prefix_k=pk, prefix_v=pv,
        cache_len=pstate.cache_len.at[slot].set(
            jnp.asarray(real_len).astype(jnp.int32)),
        last_token=pstate.last_token.at[slot].set(tok0),
        last_hidden=pstate.last_hidden.at[slot].set(
            h.astype(pstate.last_hidden.dtype)),
        rng=rng)
