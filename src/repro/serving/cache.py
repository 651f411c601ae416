"""Cache commit logic for speculative decoding.

After a verify forward pass the per-group caches hold *candidates*:

  attention groups ('k'/'v'): the cache arrays with all T tree tokens
    written in the scratch region [len, len+T); commit compacts the accepted
    root-path entries to [len, len+n_accept+1).
  state groups ('conv_win'/'wkv_state'/'shift_*'): stacked per-token
    candidate states on a T axis; commit selects the state of the last
    accepted node.  Mamba2's 'ssd_state' comes back as the chain's inputs
    instead (``kernels/ssd_chain``): commit replays the accepted prefix from
    the pre-verify state, which keeps T candidate states of every layer
    out of memory.

The attention rule and the selection are pure gathers, and the replay
reads each state once — which is what makes chain speculation on
SSM/hybrid architectures cheap (DESIGN.md §4).

Commit is part of the traced step and must stay that way: nothing here
may read a device value back to the host (no ``int()``/``bool()`` on
arrays, no data-dependent Python branching).  The async serve loop
(DESIGN.md §7) dispatches step k+1 before step k's results are read —
a host sync inside commit would re-serialize the pipeline it overlaps.

Commit addresses the cache in LOGICAL coordinates either way.  Dense
(``block_table`` None): each attention array is the per-slot (B, S) view
and compaction indexes it directly.  Paged: each attention array is the
global block pool ``(L, num_blocks, [Hkv,] block_size, D)`` (token axis
second-to-last, serving/paged.py) and the (B, M)
block table translates the same logical src/dst positions to (physical
block, offset) pairs — a token-granular gather/scatter inside slot-owned
scratch blocks, O(B·D1) touched entries, no dense view in between.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_chain.ops import ssd_chain_commit_rows

ATTN_KEYS = {"k", "v"}


def commit_chunk(pool, row, slot, start, length: int, *,
                 has_layer_axis: bool = True):
    """Chunk-granular prefill commit (DESIGN.md §8): copy the region
    ``[start, start + length)`` of a per-slot row cache back into the
    dense pool at row ``slot``.

    pool: (L, B, S, ...); row: (L, 1, S, ...) — the slot's strip after a
    ``forward`` prefill-continuation chunk (``has_layer_axis=False`` for
    the un-stacked Hydra++ prefix cache, (B, S, ...)).  Only the chunk's
    positions move (an O(length) dynamic-slice pair, not an O(S)
    whole-row scatter), so per-chunk commit traffic is proportional to
    the chunk, and the positions an interleaved decode step may have
    scribbled on beyond the prefill cursor are exactly the ones the next
    chunk overwrites.  Like every commit this is traced code: no host
    reads, no data-dependent branching (the async contract, see module
    docstring)."""
    if not has_layer_axis:
        pool, row = pool[None], row[None]
    piece = jax.lax.dynamic_slice_in_dim(row[:, 0], start, length, axis=1)
    idx = (jnp.int32(0), slot, start) + (jnp.int32(0),) * (pool.ndim - 3)
    out = jax.lax.dynamic_update_slice(
        pool, piece[:, None].astype(pool.dtype), idx)
    return out if has_layer_axis else out[0]


def _commit_attn(arr, cache_len, path_nodes, *, has_layer_axis: bool,
                 block_table=None):
    """Gather accepted tree slots to the front of the scratch region.
    arr: dense (L,B,S,...) / (B,S,...), or — with ``block_table`` — the
    pool (L,N,[Hkv,]bs,D) / (N,[Hkv,]bs,D)."""
    if not has_layer_axis:
        arr = arr[None]
    D1 = path_nodes.shape[1]
    if block_table is None:
        L, B, S = arr.shape[:3]
        bidx = jnp.arange(B)[:, None]                      # (B,1)
        src = jnp.minimum(cache_len[:, None] + path_nodes, S - 1)   # (B,D1)
        dst = jnp.minimum(cache_len[:, None] + jnp.arange(D1)[None, :], S - 1)
        vals = arr[:, bidx, src]                           # (L,B,D1,...)
        out = arr.at[:, bidx, dst].set(vals)
    else:
        bs = arr.shape[-2]
        M = block_table.shape[1]
        cap = M * bs
        src = jnp.minimum(cache_len[:, None] + path_nodes, cap - 1)
        dst = jnp.minimum(cache_len[:, None] + jnp.arange(D1)[None, :],
                          cap - 1)
        sblk = jnp.take_along_axis(block_table, src // bs, axis=1)  # (B,D1)
        dblk = jnp.take_along_axis(block_table, dst // bs, axis=1)
        # the same (block, ..., offset) index on both sides, so vals has
        # exactly the shape the scatter expects
        vals = arr[:, sblk, ..., src % bs, :]
        # released rows hold all-NULL tables: their writes collide inside
        # the shared garbage block, which is never read unmasked
        out = arr.at[:, dblk, ..., dst % bs, :].set(vals)
    return out if has_layer_axis else out[0]


def _commit_state(arr, last_node):
    """arr: (L,B,T,...) per-token candidates -> select last accepted node."""
    L, B, T = arr.shape[:3]
    bidx = jnp.arange(B)
    return arr[:, bidx, jnp.minimum(last_node, T - 1)]     # (L,B,...)


def commit_cache(candidates, cache_len, path_nodes, n_accept, *,
                 active=None, prev=None, block_table=None):
    """candidates: cache pytree from a verify forward. Returns the committed
    cache (same structure as the pre-verify committed cache).

    Attention compaction gathers accepted scratch entries [len+path] to
    [len, len+n_accept+1) in logical coordinates; with ``block_table``
    set the arrays are block pools and both sides of the move are
    translated through the table (see ``_commit_attn``).  Either way
    nothing below ``cache_len`` is touched.

    ``active`` (B,) bool + ``prev`` (pre-verify committed cache) support
    continuous batching: rows with ``active=False`` must come out of the
    commit untouched.  Attention groups already do — their compaction only
    writes the scratch region [len, len+D1), which is beyond the frozen
    ``cache_len`` (for a paged released slot those writes land in the
    shared NULL block, which is never read unmasked) — but state groups
    REPLACE the committed recurrent state with a candidate, so inactive
    rows are restored from ``prev``."""
    last_node = jnp.take_along_axis(path_nodes, n_accept[:, None],
                                    axis=1)[:, 0]          # (B,)
    out = []
    for gi, group in enumerate(candidates):
        g = {}
        for key, arr in group.items():
            if key in ATTN_KEYS:
                g[key] = _commit_attn(arr, cache_len, path_nodes,
                                      has_layer_axis=True,
                                      block_table=block_table)
            elif isinstance(arr, dict):        # a Mamba2 chain to replay
                if prev is None:    # trace-time check, never a host sync
                    raise ValueError("committing a Mamba2 chain needs prev "
                                     "(the pre-verify committed cache)")
                keep = last_node + 1
                if active is not None:
                    keep = jnp.where(active, keep, 0)
                g[key] = ssd_chain_commit_rows(arr, prev[gi][key], keep)
            else:
                new = _commit_state(arr, last_node)
                if active is not None:
                    if prev is None:    # trace-time check, never a host sync
                        raise ValueError(
                            "active-masked commit of a state group needs "
                            "prev (the pre-verify committed cache)")
                    old = prev[gi][key]
                    sel = active.reshape((1, -1) + (1,) * (new.ndim - 2))
                    new = jnp.where(sel, new, old.astype(new.dtype))
                g[key] = new
        out.append(g)
    return out


def commit_prefix_cache(k, v, cache_len, path_nodes, *, block_table=None):
    """PrefixAttention cache: accepted hiddens were processed as a CHAIN in
    path order, so entry j in the scratch region corresponds to path step j
    — compaction is the identity gather with arange.  ``block_table``: the
    prefix cache rides the same per-slot tables as the KV pools."""
    D1 = path_nodes.shape[1]
    B = cache_len.shape[0]
    ar = jnp.broadcast_to(jnp.arange(D1)[None, :], (B, D1))
    nk = _commit_attn(k, cache_len, ar, has_layer_axis=False,
                      block_table=block_table)
    nv = _commit_attn(v, cache_len, ar, has_layer_axis=False,
                      block_table=block_table)
    return nk, nv
