"""The speculative decoding step — the paper's end-to-end mechanism.

One ``spec_decode_step`` = draft (tree or chain, via Medusa/Hydra heads) ->
verify (ONE base-model forward over the T tree tokens) -> accept (greedy or
typical criterion) -> commit caches -> emit tokens.

All shapes are static: the candidate tree is a compile-time topology, the
cache is max-length with per-row ``cache_len``, acceptance compaction is
gather-based. The whole step jits once and never retraces.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.heads import (draft_tree_tokens, init_prefix_cache,
                              prefix_forward)
from repro.core.verify import greedy_verify, typical_verify
from repro.models.model import forward, init_cache
from repro.serving.cache import (ATTN_KEYS, commit_cache, commit_chunk,
                                 commit_prefix_cache)

PAD_TOKEN = -1


class DecodeState(NamedTuple):
    cache: Any                      # committed model cache
    cache_len: jnp.ndarray          # (B,)
    last_token: jnp.ndarray         # (B,) last generated, not yet forwarded
    last_hidden: jnp.ndarray        # (B, d) head-input hidden state
    prefix_k: Optional[jnp.ndarray]  # PrefixAttention cache (hydra++)
    prefix_v: Optional[jnp.ndarray]
    rng: jnp.ndarray


class StepResult(NamedTuple):
    state: DecodeState
    emitted: jnp.ndarray            # (B, D+1) tokens, PAD-filled
    n_emitted: jnp.ndarray          # (B,) = n_accept + 1 (incl. bonus)


def max_emitted_per_step(tree, *, speculative: bool = True) -> int:
    """Most tokens one decode step can commit to a row: the deepest
    root-to-leaf path fully accepted, plus the bonus token.  The async
    serving loop (DESIGN.md §7) uses this as its per-step staleness
    bound — a dispatched-but-unharvested step advances ``cache_len`` by
    at most this many positions."""
    return (tree.max_depth + 1) if speculative else 1


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def init_decode_state(params, draft_params, cfg: ModelConfig, prompt,
                      max_len: int, rng, *, greedy: bool = True):
    """prompt: (B, P) equal-length (engine pads). Runs prefill, samples the
    first token, initializes all caches."""
    B, P = prompt.shape
    pos = jnp.broadcast_to(jnp.arange(P), (B, P))
    cache = init_cache(cfg, B, max_len)
    # want_logits=False: never materialize (B, P, V) at prefill — only the
    # last position's logits are needed to sample the first token.
    out = forward(params, cfg, prompt, pos, mode="full", cache=cache,
                  want_logits=False)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["lm_head"])
    last_logits = (out.hidden[:, -1].astype(jnp.float32)
                   @ unembed.astype(jnp.float32))
    rng, sub = jax.random.split(rng)
    if greedy:
        tok0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    else:
        tok0 = jax.random.categorical(sub, last_logits).astype(jnp.int32)

    h = out.hidden[:, -1]
    pk = pv = None
    if draft_params is not None and "prefix" in draft_params:
        ph, nk, nv = prefix_forward(draft_params, cfg, out.hidden, pos)
        pc = init_prefix_cache(cfg, B, max_len)
        pk = pc["k"].at[:, :P].set(nk.astype(pc["k"].dtype))
        pv = pc["v"].at[:, :P].set(nv.astype(pc["v"].dtype))
        h = ph[:, -1]
    return DecodeState(cache=out.cache,
                       cache_len=jnp.full((B,), P, jnp.int32),
                       last_token=tok0, last_hidden=h,
                       prefix_k=pk, prefix_v=pv, rng=rng)


def init_pool_state(params, draft_params, cfg: ModelConfig, max_batch: int,
                    max_len: int, rng) -> DecodeState:
    """Empty slot-pool state for a continuous-batching engine: all caches
    zeroed, every row idle (cache_len 0).  Rows become live via
    ``join_slot`` and are stepped with an ``active`` mask."""
    pk = pv = None
    if draft_params is not None and "prefix" in draft_params:
        pc = init_prefix_cache(cfg, max_batch, max_len)
        pk, pv = pc["k"], pc["v"]
    return DecodeState(
        cache=init_cache(cfg, max_batch, max_len),
        cache_len=jnp.zeros((max_batch,), jnp.int32),
        last_token=jnp.zeros((max_batch,), jnp.int32),
        last_hidden=jnp.zeros((max_batch, cfg.d_model), jnp.dtype(cfg.dtype)),
        prefix_k=pk, prefix_v=pv, rng=rng)


def _first_token(params, cfg: ModelConfig, h_last, rng, greedy: bool):
    """Sample the first token of a freshly prefilled request from the
    hidden state of its last real prompt token.  Splits ``rng`` exactly
    once per request (greedy consumes none of it, which is why scheduling
    order can never perturb greedy streams)."""
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["lm_head"])
    last_logits = h_last.astype(jnp.float32) @ unembed.astype(jnp.float32)
    rng, sub = jax.random.split(rng)
    if greedy:
        tok0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    else:
        tok0 = jax.random.categorical(sub, last_logits).astype(jnp.int32)
    return tok0, rng


def join_slot(params, draft_params, cfg: ModelConfig, state: DecodeState,
              prompt, real_len, slot, *, greedy: bool = True) -> DecodeState:
    """Prefill one request and install it in row ``slot`` of the pool.

    prompt: (P,) int32, right-padded to P; ``real_len`` <= P is the true
    prompt length (length-masked attention: with right padding and causal
    masking, positions < real_len never attend to the pad tail, and the
    pad tail's cache entries sit beyond cache_len = real_len where every
    later verify step masks or overwrites them).  P is the only shape this
    function traces on, so an engine that buckets prompt lengths compiles
    one join per bucket.  Architectures with recurrent state groups
    (mamba/rwkv) tolerate right-pad too since the length-masked scan
    (``valid_len``, models/ssm.py): state is carried past pads unchanged,
    so bucketed padding is legal for every arch.

    Async contract (DESIGN.md §7): this function performs no host reads —
    the first sampled token is *installed* in ``last_token[slot]`` rather
    than returned as a Python int, so the engine can dispatch a join into
    the device lane behind an in-flight decode step and read the token
    back one step later (``_harvest``) without flushing the pipeline.
    Under greedy decoding the sample consumes no randomness, which is why
    host-side scheduling order can never perturb the token stream.
    """
    P = prompt.shape[0]
    pos = jnp.arange(P)[None, :]
    row_cache = init_cache(cfg, 1, _pool_max_len(state))
    rl = jnp.reshape(real_len, (1,)).astype(jnp.int32)
    out = forward(params, cfg, prompt[None, :], pos, mode="full",
                  cache=row_cache, valid_len=rl, want_logits=False)
    idx = jnp.maximum(real_len - 1, 0)
    h_last = out.hidden[0, idx]
    tok0, rng = _first_token(params, cfg, h_last, state.rng, greedy)

    h = h_last
    pk, pv = state.prefix_k, state.prefix_v
    if draft_params is not None and "prefix" in draft_params:
        ph, nk, nv = prefix_forward(draft_params, cfg, out.hidden, pos)
        pk = pk.at[slot, :P].set(nk[0].astype(pk.dtype))
        pv = pv.at[slot, :P].set(nv[0].astype(pv.dtype))
        h = ph[0, idx]

    new_cache = jax.tree_util.tree_map(
        lambda pool, row: pool.at[:, slot].set(row[:, 0].astype(pool.dtype)),
        state.cache, out.cache)
    return DecodeState(
        cache=new_cache,
        cache_len=state.cache_len.at[slot].set(real_len),
        last_token=state.last_token.at[slot].set(tok0),
        last_hidden=state.last_hidden.at[slot].set(
            h.astype(state.last_hidden.dtype)),
        prefix_k=pk, prefix_v=pv, rng=rng)


def _pool_max_len(state: DecodeState) -> int:
    """Static cache capacity S of a pool state (attention caches are
    (L, B, S, ...); state-group-only archs fall back to prefix/None)."""
    for group in state.cache:
        if "k" in group:
            return group["k"].shape[2]
    if state.prefix_k is not None:
        return state.prefix_k.shape[1]
    return 1  # pure-SSM cache pytrees carry no sequence axis


# ---------------------------------------------------------------------------
# chunked (resumable) prefill — DESIGN.md §8
# ---------------------------------------------------------------------------


def join_slot_chunk(params, draft_params, cfg: ModelConfig,
                    state: DecodeState, chunk, start, real_len, slot, *,
                    final: bool, view_len: Optional[int] = None,
                    greedy: bool = True) -> DecodeState:
    """One chunk of a resumable prefill into row ``slot`` of the pool.

    ``chunk``: (C,) int32 — tokens ``[start, start + C)`` of the request's
    C-padded context; ``real_len`` is the true total context length (only
    the final chunk may carry right-pad).  The chunk runs a prefill
    *continuation* forward (``forward(mode='full', cache_len=start)``):
    attention writes the chunk K/V at ``[start, start+C)`` and attends
    with the same blocked full-seq math as a monolithic prefill,
    recurrent state scans onward from the row's carried state — so a
    prompt prefilled in chunks is byte-identical to one prefilled whole,
    and chunking is pure scheduling.

    Non-final chunks advance the prefill cursor (``cache_len[slot] =
    start + C`` — the slot stays inactive, and any scratch a concurrent
    decode step scribbles beyond the cursor is overwritten by the next
    chunk) and leave token/hidden state untouched.  The final chunk
    (``final=True`` — a second trace of the same C shape, so a chunked
    engine compiles exactly two prefill executables regardless of prompt
    length) gathers the hidden state of token ``real_len - 1``, samples
    the request's first token, and installs
    ``last_token``/``last_hidden``/``cache_len = real_len``, activating
    the slot.  Same async contract as ``join_slot``: no host reads, the
    sampled token is read back one step later at harvest.

    ``view_len`` (static) truncates the attention view of the row cache
    to its first ``view_len`` positions — it must cover ``start + C``.
    A fully-masked tail is an exact no-op of the blocked attention, so
    any covering extent yields identical bits; the engine picks the next
    power of two above the prefill cursor, which keeps per-chunk
    attention cost proportional to context actually written (instead of
    O(max_len) per chunk) at the price of one extra trace per extent —
    bounded by log2(max_len), independent of prompt lengths.
    """
    C = chunk.shape[0]
    pos = (start + jnp.arange(C))[None, :]
    start1 = jnp.reshape(start, (1,)).astype(jnp.int32)
    valid = jnp.clip(real_len - start, 0, C)
    view = slice(None, view_len)
    # the FIRST chunk must scan from a zero recurrent state — the row
    # still holds the slot's previous occupant's state (join_slot gets
    # this for free by building a fresh row; stale attention entries need
    # no reset, the kv_valid_len mask already hides them)
    fresh = jnp.asarray(start) == 0

    def _row_state(a):
        row = a[:, slot][:, None]
        return jnp.where(fresh, jnp.zeros_like(row), row)

    row_cache = [{k: (a[:, slot][:, None, view] if k in ATTN_KEYS
                      else _row_state(a))
                  for k, a in g.items()} for g in state.cache]
    out = forward(params, cfg, chunk[None, :], pos, mode="full",
                  cache=row_cache, cache_len=start1,
                  valid_len=jnp.reshape(valid, (1,)), want_logits=False)

    # chunk-granular commit: attention rows move only [start, start+C);
    # recurrent rows replace the carried state
    new_cache = []
    for gp, gr in zip(state.cache, out.cache):
        g = {}
        for key, arr in gp.items():
            if key in ATTN_KEYS:
                g[key] = commit_chunk(arr, gr[key], slot, start, C)
            else:
                g[key] = arr.at[:, slot].set(gr[key][:, 0].astype(arr.dtype))
        new_cache.append(g)

    h_seq = out.hidden
    pk, pv = state.prefix_k, state.prefix_v
    ph = None
    if draft_params is not None and "prefix" in draft_params:
        ph, nk, nv = prefix_forward(
            draft_params, cfg, h_seq, pos,
            cache_k=pk[slot][None, view], cache_v=pv[slot][None, view],
            cache_len=start1, prefill=True)
        pk = commit_chunk(pk, nk, slot, start, C, has_layer_axis=False)
        pv = commit_chunk(pv, nv, slot, start, C, has_layer_axis=False)

    if not final:
        return DecodeState(
            cache=new_cache,
            cache_len=state.cache_len.at[slot].set(
                (start + C).astype(jnp.int32)),
            last_token=state.last_token, last_hidden=state.last_hidden,
            prefix_k=pk, prefix_v=pv, rng=state.rng)

    idx = jnp.clip(valid - 1, 0, C - 1)
    h_last = h_seq[0, idx]
    tok0, rng = _first_token(params, cfg, h_last, state.rng, greedy)
    h = ph[0, idx] if ph is not None else h_last
    return DecodeState(
        cache=new_cache,
        cache_len=state.cache_len.at[slot].set(
            jnp.asarray(real_len).astype(jnp.int32)),
        last_token=state.last_token.at[slot].set(tok0),
        last_hidden=state.last_hidden.at[slot].set(
            h.astype(state.last_hidden.dtype)),
        prefix_k=pk, prefix_v=pv, rng=rng)


# ---------------------------------------------------------------------------
# the speculative step
# ---------------------------------------------------------------------------


def spec_decode_step(params, draft_params, cfg: ModelConfig, tree,
                     state: DecodeState, *, criterion: str = "greedy",
                     temperature: float = 0.7, epsilon: float = 0.15,
                     alpha: Optional[float] = None,
                     active: Optional[jnp.ndarray] = None,
                     block_table: Optional[jnp.ndarray] = None) -> StepResult:
    """``active`` (B,) bool: rows that hold a live request.  Inactive rows
    ride along in the batch (the forward still runs over them — shapes are
    static) but emit PAD, advance no cache, and keep their state bit-frozen,
    which is what lets a continuous-batching engine free and refill slots
    without retracing.  ``active=None`` means all rows live (legacy path).

    ``block_table`` (B, M) int32 switches the cache layout: ``state.cache``
    attention arrays (and the Hydra++ prefix cache) are then global block
    pools streamed through the table by the native paged kernel, and the
    commit compaction moves accepted entries inside slot-owned blocks —
    the whole step runs without ever assembling a dense per-slot view."""
    B = state.last_token.shape[0]
    T = tree.size
    depth = jnp.asarray(tree.depth)
    tm = jnp.asarray(tree.ancestor_mask)

    # each phase runs under a ``jax.named_scope``: the op metadata, and so
    # the profiler's view of the step, names it (no effect on the math)
    # 1. draft: populate the candidate tree (root = last_token)
    with jax.named_scope("draft"):
        tokens, draft_logp = draft_tree_tokens(
            draft_params, cfg, params, tree, state.last_hidden,
            state.last_token)

    # 2. verify: one base forward over the T tree tokens
    with jax.named_scope("verify"):
        positions = state.cache_len[:, None] + depth[None, :]
        out = forward(params, cfg, tokens, positions, mode="verify",
                      cache=state.cache, cache_len=state.cache_len,
                      tree_mask=tm, block_table=block_table)

    # 3. accept
    with jax.named_scope("accept"):
        rng, sub = jax.random.split(state.rng)
        if criterion == "greedy":
            res = greedy_verify(tree, tokens, out.logits)
        elif criterion == "typical":
            res = typical_verify(tree, tokens, out.logits, sub,
                                 temperature=temperature, epsilon=epsilon,
                                 alpha=alpha)
        else:
            raise ValueError(criterion)

    # 4. commit
    with jax.named_scope("commit"):
        new_cache = commit_cache(out.cache, state.cache_len, res.path_nodes,
                                 res.n_accept, active=active,
                                 prev=state.cache, block_table=block_table)
        D1 = res.path_nodes.shape[1]
        bidx = jnp.arange(B)[:, None]
        acc_hidden = out.hidden[bidx, res.path_nodes]      # (B, D1, d)

    if draft_params is not None and "prefix" in draft_params:
        with jax.named_scope("draft_prefix"):
            ppos = state.cache_len[:, None] + jnp.arange(D1)[None, :]
            ph, nk, nv = prefix_forward(
                draft_params, cfg, acc_hidden, ppos,
                cache_k=state.prefix_k, cache_v=state.prefix_v,
                cache_len=state.cache_len, tree_mask=None,  # chain mask
                block_table=block_table)
            pk, pv = commit_prefix_cache(nk, nv, state.cache_len,
                                         res.path_nodes,
                                         block_table=block_table)
            h_next = jnp.take_along_axis(
                ph, res.n_accept[:, None, None], axis=1)[:, 0]
    else:
        pk, pv = state.prefix_k, state.prefix_v
        h_next = jnp.take_along_axis(
            acc_hidden, res.n_accept[:, None, None], axis=1)[:, 0]

    # 5. emitted tokens this step: accepted candidates then the bonus token
    with jax.named_scope("commit"):
        tok_path = tokens[bidx, res.path_nodes]            # (B, D1)
        j = jnp.arange(D1)[None, :]
        shifted = jnp.concatenate(
            [tok_path[:, 1:], jnp.full((B, 1), PAD_TOKEN, jnp.int32)], 1)
        emitted = jnp.where(j < res.n_accept[:, None], shifted, PAD_TOKEN)
        emitted = jnp.where(j == res.n_accept[:, None],
                            res.bonus_token[:, None], emitted)

    n_emitted = res.n_accept + 1
    cache_len = state.cache_len + n_emitted
    last_token, last_hidden = res.bonus_token, h_next
    if active is not None:
        # freeze inactive rows: attention commits only touched their scratch
        # region (beyond cache_len, masked out by every later step) and the
        # state-group commit already kept `prev`, so pinning the per-row
        # scalars/hidden is all that is left.
        emitted = jnp.where(active[:, None], emitted, PAD_TOKEN)
        n_emitted = jnp.where(active, n_emitted, 0)
        cache_len = jnp.where(active, cache_len, state.cache_len)
        last_token = jnp.where(active, last_token, state.last_token)
        last_hidden = jnp.where(active[:, None], last_hidden,
                                state.last_hidden)

    new_state = DecodeState(
        cache=new_cache,
        cache_len=cache_len,
        last_token=last_token,
        last_hidden=last_hidden,
        prefix_k=pk, prefix_v=pv, rng=rng)
    return StepResult(new_state, emitted, n_emitted)


# ---------------------------------------------------------------------------
# autoregressive baseline step (T=1 "tree")
# ---------------------------------------------------------------------------


def autoregressive_step(params, cfg: ModelConfig, state: DecodeState, *,
                        greedy: bool = True, temperature: float = 1.0,
                        active: Optional[jnp.ndarray] = None,
                        block_table: Optional[jnp.ndarray] = None
                        ) -> StepResult:
    B = state.last_token.shape[0]
    tokens = state.last_token[:, None]
    positions = state.cache_len[:, None]
    out = forward(params, cfg, tokens, positions, mode="verify",
                  cache=state.cache, cache_len=state.cache_len,
                  tree_mask=None, block_table=block_table)
    rng, sub = jax.random.split(state.rng)
    logits = out.logits[:, 0]
    if greedy:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        nxt = jax.random.categorical(sub, logits / temperature
                                     ).astype(jnp.int32)
    path = jnp.zeros((B, 1), jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)
    new_cache = commit_cache(out.cache, state.cache_len, path, zero,
                             active=active, prev=state.cache,
                             block_table=block_table)
    emitted = nxt[:, None]
    n_emitted = jnp.ones((B,), jnp.int32)
    cache_len = state.cache_len + 1
    last_hidden = out.hidden[:, 0]
    if active is not None:
        emitted = jnp.where(active[:, None], emitted, PAD_TOKEN)
        n_emitted = jnp.where(active, n_emitted, 0)
        cache_len = jnp.where(active, cache_len, state.cache_len)
        nxt = jnp.where(active, nxt, state.last_token)
        last_hidden = jnp.where(active[:, None], last_hidden,
                                state.last_hidden)
    new_state = DecodeState(
        cache=new_cache, cache_len=cache_len, last_token=nxt,
        last_hidden=last_hidden, prefix_k=state.prefix_k,
        prefix_v=state.prefix_v, rng=rng)
    return StepResult(new_state, emitted, n_emitted)


# ---------------------------------------------------------------------------
# generation loop (python-level; the step itself is jitted once)
# ---------------------------------------------------------------------------


def generate(params, draft_params, cfg: ModelConfig, tree, prompt, *,
             max_new_tokens: int = 64, max_len: int = 1024, rng=None,
             criterion: str = "greedy", use_speculative: bool = True,
             temperature: float = 0.7, epsilon: float = 0.15):
    """Returns (tokens (B, max_new_tokens), steps_taken, accept_lengths)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    state = init_decode_state(params, draft_params, cfg, prompt, max_len,
                              rng, greedy=(criterion == "greedy"))
    B = prompt.shape[0]

    if use_speculative:
        # cfg/tree are static topology — capture them in the jitted closure
        step_fn = jax.jit(lambda p, dp, st: spec_decode_step(
            p, dp, cfg, tree, st, criterion=criterion,
            temperature=temperature, epsilon=epsilon))

        def run_step(st):
            return step_fn(params, draft_params, st)
    else:
        ar_fn = jax.jit(lambda p, st: autoregressive_step(
            p, cfg, st, greedy=(criterion == "greedy"),
            temperature=temperature))

        def run_step(st):
            return ar_fn(params, st)

    outs = [state.last_token[:, None]]  # first token from prefill
    produced = 1
    steps = 0
    accept_lens = []
    while produced < max_new_tokens:
        state, emitted, n_em = run_step(state)
        outs.append(emitted)
        accept_lens.append(n_em)
        produced += int(n_em.min())
        steps += 1
        if steps > 4 * max_new_tokens:
            break
    toks = jnp.concatenate(outs, axis=1)
    acc = (jnp.stack(accept_lens, 1).astype(jnp.float32)
           if accept_lens else jnp.ones((B, 1)))
    return toks, steps, acc
