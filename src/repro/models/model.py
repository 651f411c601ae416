"""Composable model assembly for all assigned architectures.

A model is a sequence of *groups*; repeated layers inside a group are stacked
on a leading axis and executed with ``lax.scan`` (keeps 64-layer × 512-device
HLO compact).  Group kinds:

  attn_stack   — pre-norm transformer layers (GQA or MLA; dense or MoE FFN)
  mamba_stack  — Mamba2 layers
  rwkv_stack   — RWKV6 layers (time-mix + channel-mix)
  shared_attn  — zamba2's shared transformer block (weights shared across
                 invocations; distinct KV-cache slot per invocation)

Execution modes:
  'full'   — train/prefill over the whole sequence (blocked attention /
             chunked ssm scan); optionally fills a cache (prefill)
  'verify' — T speculative tokens (tree or chain) against a populated cache;
             SSM groups additionally return per-token candidate states so
             acceptance can roll back (see serving/cache.py::commit_cache)
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (AttnInputs, gqa_fwd, init_gqa, init_mla,
                                    mla_fwd)
from repro.models.layers import embed_init, init_mlp, mlp_fwd, rms_norm
from repro.models.moe import init_moe, moe_fwd
from repro.models.ssm import (_gather_last_valid, init_mamba2, init_rwkv6,
                              mamba2_fwd, mamba2_dims, rwkv6_chanmix,
                              rwkv6_timemix)


class ModelOutputs(NamedTuple):
    hidden: jnp.ndarray                  # (B, T, d) final-norm hidden states
    logits: Optional[jnp.ndarray]        # (B, T, V) fp32
    cache: Any                           # updated cache pytree (or None)
    aux_loss: jnp.ndarray                # MoE load-balance aux


# ---------------------------------------------------------------------------
# group program
# ---------------------------------------------------------------------------


def group_program(cfg: ModelConfig):
    """Returns a list of (kind, n_layers) describing the stack."""
    if cfg.block_kind == "rwkv6":
        return [("rwkv_stack", cfg.n_layers)]
    if cfg.block_kind == "mamba2":
        groups = []
        every = cfg.hybrid_attn_every
        if not every:
            return [("mamba_stack", cfg.n_layers)]
        done = 0
        while done < cfg.n_layers:
            seg = min(every, cfg.n_layers - done)
            groups.append(("shared_attn", 1))
            groups.append(("mamba_stack", seg))
            done += seg
        return groups
    if cfg.moe:
        nd = cfg.moe.n_dense_layers
        out = []
        if nd:
            out.append(("attn_stack_dense", nd))
        out.append(("attn_stack_moe", cfg.n_layers - nd))
        return out
    return [("attn_stack_dense", cfg.n_layers)]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_attn_layer(key, cfg, dtype, moe_ffn: bool):
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": jnp.zeros((cfg.d_model,), dtype),
        "norm2": jnp.zeros((cfg.d_model,), dtype),
        "attn": (init_mla(k1, cfg, dtype) if cfg.mla
                 else init_gqa(k1, cfg, dtype)),
    }
    if moe_ffn:
        p["moe"] = init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _stack_init(fn, key, n):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key, cfg: ModelConfig):
    """Random base-model parameters in ``cfg.dtype``.  Built under jit, so
    each weight is drawn, scaled and cast in one fused pass: no float32
    copy of a stacked layer matrix is ever materialised, which is what
    lets a full-width model initialise on the chip that serves it."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8 + len(group_program(cfg)))
    params: dict = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], cfg.vocab_size, cfg.d_model,
                                       dtype).T
    if cfg.modality == "audio":
        params["mask_embed"] = (jax.random.normal(keys[2], (cfg.d_model,))
                                * 0.02).astype(dtype)

    groups = []
    prog = group_program(cfg)
    shared_attn_params = None
    for gi, (kind, n) in enumerate(prog):
        gk = keys[4 + gi]
        if kind == "attn_stack_dense":
            groups.append(_stack_init(
                lambda k: _init_attn_layer(k, cfg, dtype, moe_ffn=False), gk, n))
        elif kind == "attn_stack_moe":
            groups.append(_stack_init(
                lambda k: _init_attn_layer(k, cfg, dtype, moe_ffn=True), gk, n))
        elif kind == "mamba_stack":
            groups.append(_stack_init(
                lambda k: {"norm": jnp.zeros((cfg.d_model,), dtype),
                           "mamba": init_mamba2(k, cfg, dtype)}, gk, n))
        elif kind == "rwkv_stack":
            groups.append(_stack_init(
                lambda k: {"norm1": jnp.zeros((cfg.d_model,), dtype),
                           "norm2": jnp.zeros((cfg.d_model,), dtype),
                           "rwkv": init_rwkv6(k, cfg, dtype)}, gk, n))
        elif kind == "shared_attn":
            if shared_attn_params is None:
                shared_attn_params = _init_attn_layer(keys[3], cfg, dtype,
                                                      moe_ffn=False)
            groups.append({})                      # weights live in shared slot
    params["groups"] = groups
    if shared_attn_params is not None:
        params["shared_attn"] = shared_attn_params
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None):
    """Committed cache pytree: one entry per group."""
    if not cfg.supports_decode:
        return None
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    caches = []
    for kind, n in group_program(cfg):
        if kind.startswith("attn_stack"):
            if cfg.mla:
                m = cfg.mla
                caches.append({
                    "k": jnp.zeros((n, batch, max_len, m.kv_lora_rank), dtype),
                    "v": jnp.zeros((n, batch, max_len, m.qk_rope_dim), dtype),
                })
            else:
                caches.append({
                    "k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
                    "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
                })
        elif kind == "shared_attn":
            caches.append({
                "k": jnp.zeros((1, batch, max_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((1, batch, max_len, cfg.n_kv_heads, hd), dtype),
            })
        elif kind == "mamba_stack":
            s = cfg.ssm
            d_in, H, conv_ch = mamba2_dims(cfg)
            caches.append({
                "ssd_state": jnp.zeros((n, batch, H, s.d_state, s.head_dim),
                                       jnp.float32),
                "conv_win": jnp.zeros((n, batch, s.conv_width - 1, conv_ch),
                                      dtype),
            })
        elif kind == "rwkv_stack":
            H = cfg.n_heads
            hd_r = cfg.d_model // H
            caches.append({
                "wkv_state": jnp.zeros((n, batch, H, hd_r, hd_r), jnp.float32),
                "shift_tm": jnp.zeros((n, batch, 1, cfg.d_model), dtype),
                "shift_cm": jnp.zeros((n, batch, 1, cfg.d_model), dtype),
            })
    return caches


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------


def _attn_layer_fwd(lp, cfg, h, ai: AttnInputs, moe_ffn: bool):
    fwd = mla_fwd if cfg.mla else gqa_fwd
    a, nk, nv = fwd(lp["attn"], cfg, rms_norm(h, lp["norm1"], cfg.rms_eps), ai)
    h = h + a
    aux = jnp.zeros((), jnp.float32)
    x2 = rms_norm(h, lp["norm2"], cfg.rms_eps)
    if moe_ffn:
        f, aux = moe_fwd(lp["moe"], cfg, x2)
    else:
        f = mlp_fwd(lp["mlp"], x2)
    return h + f, nk, nv, aux


def _window_array(cfg, n_layers, offset=0):
    return jnp.array([cfg.window_for_layer(i + offset)
                      for i in range(n_layers)], jnp.int32)


def paged_kernel_covers(cfg: ModelConfig, offset: int = 0,
                        n: Optional[int] = None) -> bool:
    """True when the native paged attention-template instantiations cover
    layers ``[offset, offset + n)`` (default: the whole model) — i.e.
    none of them takes the per-layer gather fallback.  Since the
    attention-template refactor (DESIGN.md §11) that is EVERY layer:
    sliding-window groups run the windowed instantiation (the window is
    a traced operand) and MLA runs the absorbed-latent instantiation, so
    this is identically True.  Kept as the single source of truth the
    paged engine keys its transient-memory accounting off
    (serving/engine.py) — and as the seam a future variant outside the
    template's reach would reopen."""
    del cfg, offset, n
    return True


def group_has_window(cfg: ModelConfig, offset: int, n: int) -> bool:
    """True when any layer in ``[offset, offset + n)`` is sliding-window:
    the group's verify path then takes the windowed template variant
    (window rides as a traced scan operand; 0 is an exact mask no-op for
    the group's global layers)."""
    return any(cfg.window_for_layer(offset + i) > 0 for i in range(n))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, inputs, positions, *, mode: str = "full",
            cache=None, cache_len=None, tree_mask=None, block_table=None,
            valid_len=None, want_logits: bool = True):
    """inputs: (B,T) int tokens, or (B,T,d) embeddings (audio frontend stub).

    mode='full':  causal (or bidirectional for encoder_only) over T tokens.
                  If `cache` is given, it is filled at positions [0, T)
                  (prefill) and returned.  Passing `cache_len` (B,) as
                  well switches to **prefill continuation** (DESIGN.md
                  §8): the T tokens are one CHUNK at absolute positions
                  `cache_len + arange(T)`; attention groups write the
                  chunk K/V into the populated cache and attend with the
                  same blocked full-seq math as plain prefill (masked past
                  `cache_len + T`), recurrent groups scan onward from the
                  cached state.  `block_table` is honored here too, so a
                  paged chunk writes token-granular through the table
                  (no dense join strip).
    mode='verify': T speculative tokens against the populated cache;
                  `cache_len` (B,) is the committed length; `tree_mask`
                  (T,T) ancestor mask (None => chain / plain decode).
                  `block_table` (B, M) int32 switches attention groups to
                  the paged cache layout: their `cache` arrays are global
                  block pools `(L, num_blocks, block_size, ...)` streamed
                  through the table by the native paged tree-attention
                  kernel (recurrent-state groups stay dense per-slot and
                  ignore the table).

    `valid_len` (B,), full mode only: true number of non-pad tokens among
    the T inputs.  Attention needs no masking for right-pads (causality
    hides them); recurrent-state groups length-mask their scan so state
    is carried past pads unchanged and final states are taken at
    `valid_len - 1` (models/ssm.py) — this is what lets bucketed/chunked
    prefill pad mamba2/rwkv6 prompts.
    """
    assert mode in ("full", "verify")
    is_chunk = mode == "full" and cache is not None and cache_len is not None
    assert block_table is None or mode == "verify" or is_chunk, \
        "paged layout needs verify mode or a prefill continuation"
    B, T = inputs.shape[:2]
    if inputs.ndim == 2:
        h = params["embed"][inputs]
    else:
        h = inputs.astype(jnp.dtype(cfg.dtype))

    is_verify = mode == "verify"
    if is_verify:
        assert cache is not None and cache_len is not None
    causal = not cfg.encoder_only
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = [] if cache is not None else None

    prog = group_program(cfg)
    layer_offset = 0
    shared_inv = 0
    for gi, (kind, n) in enumerate(prog):
        gp = params["groups"][gi]
        gc = cache[gi] if cache is not None else None

        if kind.startswith("attn_stack"):
            moe_ffn = kind.endswith("moe")
            windows = _window_array(cfg, n, layer_offset)
            # static dispatch: every group runs a native paged template
            # instantiation; groups with sliding-window layers take the
            # WINDOWED variant (window is a traced scan operand, so the
            # choice is per GROUP at trace time — one compiled kernel
            # serves a group mixing local+global layers, e.g. gemma3's
            # 5:1 pattern, with window 0 an exact mask no-op).
            win_group = group_has_window(cfg, layer_offset, n)

            def body(carry, xs):
                h, aux = carry
                lp, win, ck, cv = xs
                ai = AttnInputs(
                    q_pos=positions, cache_k=ck, cache_v=cv,
                    cache_len=cache_len,
                    tree_mask=tree_mask if is_verify else None,
                    window=win, causal=causal,
                    block_table=block_table,
                    windowed=win_group, prefill=is_chunk)
                h, nk, nv, aux_l = _attn_layer_fwd(lp, cfg, h, ai, moe_ffn)
                return (h, aux + aux_l), (nk, nv)

            if is_verify or is_chunk:
                xs = (gp, windows, gc["k"], gc["v"])
                (h, aux_total), (nk, nv) = jax.lax.scan(
                    body, (h, aux_total), xs)
                new_cache.append({"k": nk, "v": nv})
            else:
                fill = cache is not None

                def body_full(carry, xs_):
                    lp, win = xs_
                    h, aux = carry
                    ai = AttnInputs(q_pos=positions, cache_k=None,
                                    cache_v=None, cache_len=None,
                                    tree_mask=None, window=win, causal=causal)
                    h, nk, nv, aux_l = _attn_layer_fwd(lp, cfg, h, ai, moe_ffn)
                    # don't stack K/V activations when nobody consumes them
                    return (h, aux + aux_l), ((nk, nv) if fill else None)

                (h, aux_total), ys = jax.lax.scan(
                    jax.checkpoint(body_full), (h, aux_total),
                    (gp, windows))
                nk, nv = ys if fill else (None, None)
                if cache is not None:  # prefill: write [0, T)
                    S = gc["k"].shape[2]
                    if cfg.mla:
                        new_cache.append({
                            "k": gc["k"].at[:, :, :T].set(
                                nk.astype(gc["k"].dtype)),
                            "v": gc["v"].at[:, :, :T].set(
                                nv.astype(gc["v"].dtype))})
                    else:
                        new_cache.append({
                            "k": gc["k"].at[:, :, :T].set(
                                nk.astype(gc["k"].dtype)),
                            "v": gc["v"].at[:, :, :T].set(
                                nv.astype(gc["v"].dtype))})

        elif kind == "shared_attn":
            sp = params["shared_attn"]
            win = jnp.int32(0)
            if is_verify or is_chunk:
                ai = AttnInputs(q_pos=positions, cache_k=gc["k"][0],
                                cache_v=gc["v"][0], cache_len=cache_len,
                                tree_mask=tree_mask if is_verify else None,
                                window=win, causal=True,
                                block_table=block_table, prefill=is_chunk)
                h, nk, nv, _ = _attn_layer_fwd(sp, cfg, h, ai, moe_ffn=False)
                new_cache.append({"k": nk[None], "v": nv[None]})
            else:
                ai = AttnInputs(q_pos=positions, cache_k=None, cache_v=None,
                                cache_len=None, tree_mask=None, window=win,
                                causal=True)
                h, nk, nv, _ = _attn_layer_fwd(sp, cfg, h, ai, moe_ffn=False)
                if cache is not None:
                    new_cache.append({
                        "k": gc["k"].at[:, :, :T].set(
                            nk[None].astype(gc["k"].dtype)),
                        "v": gc["v"].at[:, :, :T].set(
                            nv[None].astype(gc["v"].dtype))})
            shared_inv += 1

        elif kind == "mamba_stack":
            mmode = "verify" if is_verify else "full"
            vlen = None if is_verify else valid_len

            def mbody(h, xs):
                lp, ssd0, conv0 = xs
                x2 = rms_norm(h, lp["norm"], cfg.rms_eps)
                y, ns = mamba2_fwd(lp["mamba"], cfg, x2, mode=mmode,
                                   ssd_state=ssd0, conv_state=conv0,
                                   valid_len=vlen)
                return h + y, (ns["ssd_state"], ns["conv_win"])

            ssd0 = gc["ssd_state"] if gc is not None else jnp.zeros(
                (n, B, *init_cache_shapes_mamba(cfg)), jnp.float32)
            conv0 = gc["conv_win"] if gc is not None else jnp.zeros(
                (n, B, cfg.ssm.conv_width - 1, mamba2_dims(cfg)[2]),
                jnp.dtype(cfg.dtype))
            mbody_x = jax.checkpoint(mbody) if not is_verify else mbody
            h, (nssd, nconv) = jax.lax.scan(mbody_x, h, (gp, ssd0, conv0))
            if cache is not None:
                new_cache.append({"ssd_state": nssd, "conv_win": nconv})

        elif kind == "rwkv_stack":
            rmode = "verify" if is_verify else "full"
            vlen = None if is_verify else valid_len
            # the inner scan chunk is config-driven so a chunked prefill
            # can align its chunk size to it (state-update grouping — and
            # therefore the bits — then match the monolithic scan, §8)
            rchunk = cfg.ssm.chunk_size if cfg.ssm else 64

            def rbody(h, xs):
                lp, wkv0, stm0, scm0 = xs
                x1 = rms_norm(h, lp["norm1"], cfg.rms_eps)
                o, ns = rwkv6_timemix(lp["rwkv"], cfg, x1, mode=rmode,
                                      wkv_state=wkv0, shift_last=stm0,
                                      chunk=rchunk, valid_len=vlen)
                h = h + o
                x2 = rms_norm(h, lp["norm2"], cfg.rms_eps)
                cm = rwkv6_chanmix(lp["rwkv"], x2, shift_last=scm0)
                h = h + cm
                if rmode == "full":
                    new_scm = _gather_last_valid(x2, vlen)
                else:
                    new_scm = x2[:, :, None, :]       # per-token candidates
                return h, (ns["wkv_state"], ns["shift_tm"], new_scm)

            if gc is not None:
                wkv0, stm0, scm0 = gc["wkv_state"], gc["shift_tm"], gc["shift_cm"]
            else:
                H = cfg.n_heads
                hd_r = cfg.d_model // H
                wkv0 = jnp.zeros((n, B, H, hd_r, hd_r), jnp.float32)
                stm0 = jnp.zeros((n, B, 1, cfg.d_model), h.dtype)
                scm0 = jnp.zeros((n, B, 1, cfg.d_model), h.dtype)
            rbody_x = jax.checkpoint(rbody) if not is_verify else rbody
            h, (nwkv, nstm, nscm) = jax.lax.scan(rbody_x, h,
                                                 (gp, wkv0, stm0, scm0))
            if cache is not None:
                new_cache.append({"wkv_state": nwkv, "shift_tm": nstm,
                                  "shift_cm": nscm})

        layer_offset += n if kind != "shared_attn" else 0

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    logits = None
    if want_logits:
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["lm_head"])
        logits = (h.astype(jnp.float32) @ unembed.astype(jnp.float32))
    return ModelOutputs(hidden=h, logits=logits, cache=new_cache,
                        aux_loss=aux_total)


def init_cache_shapes_mamba(cfg):
    s = cfg.ssm
    _, H, _ = mamba2_dims(cfg)
    return (H, s.d_state, s.head_dim)
