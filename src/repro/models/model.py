"""Composable model assembly for all assigned architectures.

A model is a sequence of *groups*; repeated layers inside a group are stacked
on a leading axis and executed with ``lax.scan`` (keeps 64-layer × 512-device
HLO compact).  Group kinds:

  attn_stack   — pre-norm transformer layers (GQA or MLA; dense or MoE FFN)
  mamba_stack  — Mamba2 layers
  rwkv_stack   — RWKV6 layers (time-mix + channel-mix)
  hybrid       — zamba2: one invocation of a shared attention+MLP block
                 (``hybrid_block_fwd``; weights shared by the invocations
                 of a block, a KV cache, LoRA adapter and output projection
                 per invocation) followed by one Mamba2 layer whose input
                 it adds to

Execution modes:
  'full'   — train/prefill over the whole sequence (blocked attention /
             chunked ssm scan); optionally fills a cache (prefill)
  'verify' — T speculative tokens (tree or chain) against a populated cache;
             SSM groups additionally return per-token candidate states
             (Mamba2: the chain's inputs, to replay) so acceptance can roll
             back (see serving/cache.py::commit_cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (AttnInputs, gqa_fwd, init_gqa, init_mla,
                                    lane_width, mla_fwd)
from repro.models.layers import (dense_init, embed_init, init_mlp, mlp_fwd,
                                 rms_norm)
from repro.models.moe import init_moe, moe_fwd
from repro.models.ssm import (_gather_last_valid, init_mamba2, init_rwkv6,
                              mamba2_fwd, mamba2_dims, rwkv6_chanmix,
                              rwkv6_timemix, ssd_state_shape)


class ModelOutputs(NamedTuple):
    hidden: jnp.ndarray                  # (B, T, d) final-norm hidden states
    logits: Optional[jnp.ndarray]        # (B, T, V) fp32
    cache: Any                           # updated cache pytree (or None)
    aux_loss: jnp.ndarray                # MoE load-balance aux


# ---------------------------------------------------------------------------
# group program
# ---------------------------------------------------------------------------


def group_program(cfg: ModelConfig):
    """Returns a list of (kind, n_layers) describing the stack.  A hybrid
    backbone runs its layers in order: each layer of ``hybrid_layer_ids``
    is a ``hybrid`` group of its own, the runs between them
    ``mamba_stack`` groups."""
    if cfg.block_kind == "rwkv6":
        return [("rwkv_stack", cfg.n_layers)]
    if cfg.block_kind == "mamba2":
        groups = []
        for layer in range(cfg.n_layers):
            if layer in cfg.hybrid_layer_ids:
                groups.append(("hybrid", 1))
            elif groups and groups[-1][0] == "mamba_stack":
                groups[-1] = ("mamba_stack", groups[-1][1] + 1)
            else:
                groups.append(("mamba_stack", 1))
        return groups
    if cfg.moe:
        nd = cfg.moe.n_dense_layers
        out = []
        if nd:
            out.append(("attn_stack_dense", nd))
        out.append(("attn_stack_moe", cfg.n_layers - nd))
        return out
    return [("attn_stack_dense", cfg.n_layers)]


def hybrid_scale(cfg: ModelConfig) -> float:
    """The shared blocks' score scale, (head_dim / 2) ** -1/2, as
    transformers' ``modeling_zamba2.py`` computes it."""
    return (cfg.resolved_head_dim / 2) ** -0.5


def hybrid_kv_width(cfg: ModelConfig) -> int:
    """Head width of the shared blocks' KV cache: the head rounded up to
    whole 128-lane tiles (zamba2-7b: 224 -> 256, 14% more pool bytes), as
    the paged tree kernel copies pages of whole lane tiles only."""
    return lane_width(cfg.resolved_head_dim)


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


def _init_shared_block(key, cfg, dtype):
    """One shared block: attention over [h ‖ embedding] (2d wide) into d,
    then a gated MLP."""
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    wide = dataclasses.replace(cfg, d_model=2 * d)
    attn = init_gqa(k1, wide, dtype)
    attn["wo"] = dense_init(jax.random.fold_in(k1, 1),
                            cfg.n_heads * cfg.resolved_head_dim, d, dtype)
    return {"norm_in": jnp.zeros((2 * d,), dtype), "attn": attn,
            "norm_ff": jnp.zeros((d,), dtype),
            "mlp": init_mlp(k2, d, cfg.d_ff, dtype)}


def _init_mamba_layer(key, cfg, dtype):
    return {"norm": jnp.zeros((cfg.d_model,), dtype),
            "mamba": init_mamba2(key, cfg, dtype)}


def _init_hybrid_group(key, cfg, dtype):
    """A hybrid layer's own weights: the LoRA adapter of its invocation's
    MLP gate/up (A_j, B_j), its output projection Lin_j, and its Mamba2
    layer (a stack of one)."""
    d, r = cfg.d_model, cfg.adapter_rank
    ks = jax.random.split(key, 4)
    return {"linear": dense_init(ks[0], d, d, dtype),
            "lora_a": dense_init(ks[2], d, r, dtype),
            "lora_b": dense_init(ks[3], r, 2 * cfg.d_ff, dtype),
            "layers": _stack_init(lambda k: _init_mamba_layer(k, cfg, dtype),
                                  ks[1], 1)}


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
                lambda k: _init_mamba_layer(k, cfg, dtype), gk, n))
        elif kind == "rwkv_stack":
            groups.append(_stack_init(
                lambda k: {"norm1": jnp.zeros((cfg.d_model,), dtype),
                           "norm2": jnp.zeros((cfg.d_model,), dtype),
                           "rwkv": init_rwkv6(k, cfg, dtype)}, gk, n))
        elif kind == "hybrid":
            groups.append(_init_hybrid_group(gk, cfg, dtype))
    params["groups"] = groups
    if cfg.hybrid_layer_ids:
        params["shared"] = [
            _init_shared_block(jax.random.fold_in(keys[3], b), cfg, dtype)
            for b in range(cfg.num_mem_blocks)]
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
        elif kind in ("mamba_stack", "hybrid"):
            s = cfg.ssm
            conv_ch = mamba2_dims(cfg)[2]
            c = {"ssd_state": jnp.zeros((n, batch, *ssd_state_shape(cfg)),
                                        jnp.float32),
                 "conv_win": jnp.zeros((n, batch, s.conv_width - 1, conv_ch),
                                       dtype)}
            if kind == "hybrid":
                kv = (1, batch, max_len, cfg.n_kv_heads, hybrid_kv_width(cfg))
                c.update(k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype))
            caches.append(c)
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


def hybrid_block_fwd(sp, gp, cfg, h, emb, ai: AttnInputs):
    """One invocation of a shared block (Zamba2): returns (t, new_k,
    new_v), t = Lin_j(MLP_j(RMSNorm(Attn(RMSNorm([h ‖ e]))))) where e is
    the embedding, Attn runs the block's weights ``sp`` over the
    invocation's own cache (``ai``) with scores scaled by
    ``hybrid_scale``, and MLP_j is the block's GELU-gated MLP with the
    invocation's LoRA adapter added to its gate/up projection:
    [g ‖ u] = m W_gu + (m A_j) B_j.  t feeds the input of the
    invocation's Mamba2 layer, not the residual stream."""
    u = rms_norm(jnp.concatenate([h, emb], axis=-1), sp["norm_in"],
                 cfg.rms_eps)
    a, nk, nv = gqa_fwd(sp["attn"], cfg, u, ai, scale=hybrid_scale(cfg),
                        kv_width=hybrid_kv_width(cfg))
    m = rms_norm(a, sp["norm_ff"], cfg.rms_eps)
    f = sp["mlp"]
    lo = (m @ gp["lora_a"]) @ gp["lora_b"]
    g = m @ f["w_gate"] + lo[..., :cfg.d_ff]
    up = m @ f["w_up"] + lo[..., cfg.d_ff:]
    g = jax.nn.gelu(g, approximate=False)         # the exact, erf form
    return ((g * up) @ f["w_down"]) @ gp["linear"], nk, nv


def _mamba_scan(layers, cfg, h, gc, n, B, *, is_verify, valid_len,
                inject=None):
    """Run a stack of ``n`` Mamba2 layers h += Mamba2(RMSNorm(x)) under a
    ``lax.scan``, x = h (+ ``inject``, a hybrid block's output, for the
    one layer of a hybrid group).  Returns (h, ssd_state, conv_win): the
    final states (full mode) or the per-token candidates (verify)."""
    mmode = "verify" if is_verify else "full"
    vlen = None if is_verify else valid_len

    def mbody(h, xs):
        lp, ssd0, conv0 = xs
        x = h if inject is None else h + inject
        x2 = rms_norm(x, lp["norm"], cfg.rms_eps)
        with jax.named_scope("ssm"):
            y, ns = mamba2_fwd(lp["mamba"], cfg, x2, mode=mmode,
                               ssd_state=ssd0, conv_state=conv0,
                               valid_len=vlen)
        return h + y, (ns["ssd_state"], ns["conv_win"])

    ssd0 = gc["ssd_state"] if gc is not None else jnp.zeros(
        (n, B, *ssd_state_shape(cfg)), jnp.float32)
    conv0 = gc["conv_win"] if gc is not None else jnp.zeros(
        (n, B, cfg.ssm.conv_width - 1, mamba2_dims(cfg)[2]),
        jnp.dtype(cfg.dtype))
    mbody_x = jax.checkpoint(mbody) if not is_verify else mbody
    h, (nssd, nconv) = jax.lax.scan(mbody_x, h, (layers, ssd0, conv0))
    return h, nssd, nconv


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
    hybrid_inv = 0
    emb = h                          # the hybrid blocks read [h ‖ embedding]
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

        elif kind == "hybrid":
            # the j-th invocation runs block j mod num_mem_blocks
            sp = params["shared"][hybrid_inv % cfg.num_mem_blocks]
            with jax.named_scope("shared_block"):
                if is_verify or is_chunk:
                    ai = AttnInputs(
                        q_pos=positions, cache_k=gc["k"][0],
                        cache_v=gc["v"][0], cache_len=cache_len,
                        tree_mask=tree_mask if is_verify else None,
                        window=jnp.int32(0), causal=True,
                        block_table=block_table, prefill=is_chunk)
                else:
                    ai = AttnInputs(q_pos=positions, cache_k=None,
                                    cache_v=None, cache_len=None,
                                    tree_mask=None, window=jnp.int32(0),
                                    causal=True)
                t, nk, nv = hybrid_block_fwd(sp, gp, cfg, h, emb, ai)
            h, nssd, nconv = _mamba_scan(gp["layers"], cfg, h, gc, 1, B,
                                         is_verify=is_verify,
                                         valid_len=valid_len, inject=t)
            if cache is not None:
                if not (is_verify or is_chunk):    # prefill: write [0, T)
                    nk = gc["k"][0].at[:, :T].set(nk.astype(gc["k"].dtype))
                    nv = gc["v"][0].at[:, :T].set(nv.astype(gc["v"].dtype))
                new_cache.append({"ssd_state": nssd, "conv_win": nconv,
                                  "k": nk[None], "v": nv[None]})
            hybrid_inv += 1

        elif kind == "mamba_stack":
            h, nssd, nconv = _mamba_scan(gp, cfg, h, gc, n, B,
                                         is_verify=is_verify,
                                         valid_len=valid_len)
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

        layer_offset += n

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    logits = None
    if want_logits:
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["lm_head"])
        logits = (h.astype(jnp.float32) @ unembed.astype(jnp.float32))
    return ModelOutputs(hidden=h, logits=logits, cache=new_cache,
                        aux_loss=aux_total)
