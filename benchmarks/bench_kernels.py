"""Kernel micro-benchmarks: interpret-mode allclose status + jnp-path
wall-clock (CPU proxy; real perf characterization is the dry-run roofline,
see benchmarks/roofline.py).

Besides the CSV rows, ``run()`` writes ``results/bench_kernels.json``
(uploaded as a CI artifact) with two gated sections:

``serve_longprompt`` — the long-prompt ragged serving sweep (random-init
vicuna-tiny, NO trained checkpoints, so CI's bench-gate job can run it):
the identical stream — every 4th prompt ~4x the mean — served unchunked
vs chunked-prefill (DESIGN.md §8), dense and paged.  Gated columns:
``ttft_ms``/``p99_itl_ms``/``us_per_tok`` within the timing tolerance —
this is what pins the chunked-prefill responsiveness win (p99
inter-token latency) against the committed baseline.

``tree_attention_paged_sweep`` — compares the three tree-attention data
paths at several pool occupancies:

  dense  — dense per-slot cache, dense kernel (the non-paged engine);
  shim   — block pool gathered to the dense view, dense kernel on the
           view (the pre-native paged path, now the parity oracle);
  paged  — native block-table kernel streaming the pool in place.

``paged_decode_variants`` — the template-only paged decode groups
(sliding-window and absorbed-MLA) native vs the gather fallback they
retired; gated on the deterministic ``step_transient_tokens_*`` model
(native must stay below fallback in the same run), parity max-err, and
tolerance-gated latency proxies.

The load-bearing column is ``transient_bytes``: the per-step K/V bytes a
path materializes/moves on top of the persistent cache.  The shim's is
the gathered view — ``max_batch × max_len``-shaped regardless of
occupancy — while the paged kernel's is the blocks its tables actually
reach below ``cache_len``, so it scales with allocated blocks.  Wall
times are CPU jnp-path proxies (the kernels themselves are verified via
max-err against their oracles, in interpret mode).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.kernels.attention_template.ops import (
    mla_attention_paged_bshd, tree_attention_paged_windowed_bshd)
from repro.kernels.attention_template.ref import (
    mla_attention_paged_ref, tree_attention_paged_windowed_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.linear_attn_chunk.kernel import linear_attn_chunk
from repro.kernels.linear_attn_chunk.ref import linear_attn_ref
from repro.kernels.tree_attention.kernel import (tree_attention,
                                                 tree_attention_paged)
from repro.kernels.tree_attention.ref import (tree_attention_paged_ref,
                                              tree_attention_ref)

RESULTS_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                            "bench_kernels.json")


def _timeit(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6  # us


def tree_attention_paged_sweep(*, B=2, Hq=4, Hkv=2, D=64, T=16,
                               max_len=512) -> list:
    """dense-vs-shim-vs-paged parity + transient-memory model, swept over
    block size and pool occupancy.  Returns JSON-able dicts."""
    key = jax.random.PRNGKey(1)
    r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)
    tm = jnp.tril(jnp.ones((T, T), bool))
    tk, tv = r(0, (B, Hkv, T, D)), r(1, (B, Hkv, T, D))
    q = r(2, (B, Hq, T, D))
    itemsize = 4                                   # float32 benchmarks
    out = []
    for bs in (16, 128):
        M = max_len // bs
        num_blocks = 1 + B * M                     # dense-equivalent pool
        pool_k, pool_v = r(3, (num_blocks, Hkv, bs, D)), r(
            4, (num_blocks, Hkv, bs, D))
        for occupancy in (0.25, 0.5, 1.0):
            lens = np.full(B, int(occupancy * max_len) - T, np.int64)
            lens = np.maximum(lens, 1)
            table = np.zeros((B, M), np.int32)
            nxt = 1
            for b in range(B):
                for j in range(-(-int(lens[b] + T) // bs)):
                    table[b, j] = nxt
                    nxt += 1
            allocated = int((table != 0).sum())
            lens_j = jnp.asarray(lens, jnp.int32)
            table_j = jnp.asarray(table)

            # the three data paths (kernels in interpret mode for max-err,
            # jnp refs for CPU wall-clock proxies)
            gather = jax.jit(lambda pk, t: pk[t].transpose(
                0, 2, 1, 3, 4).reshape(B, Hkv, M * bs, D))
            ck, cv = gather(pool_k, table_j), gather(pool_v, table_j)
            o_dense = tree_attention(q, ck, cv, tk, tv, tm, lens_j,
                                     bk=bs, interpret=True)
            o_paged = tree_attention_paged(q, pool_k, pool_v, tk, tv, tm,
                                           lens_j, table_j, interpret=True)
            err = float(jnp.max(jnp.abs(o_dense - o_paged)))

            dense_us = _timeit(
                lambda a: tree_attention_ref(a, ck, cv, tk, tv, tm, lens_j),
                q)
            shim_us = _timeit(
                lambda a: tree_attention_ref(
                    a, gather(pool_k, table_j), gather(pool_v, table_j),
                    tk, tv, tm, lens_j), q)
            paged_us = _timeit(
                lambda a: tree_attention_paged_ref(
                    a, pool_k, pool_v, tk, tv, tm, lens_j, table_j), q)

            kv_elem = Hkv * D * itemsize * 2       # K and V, per position
            blocks_touched = int(sum(-(-int(l) // bs) for l in lens))
            out.append({
                "B": B, "Hq": Hq, "Hkv": Hkv, "D": D, "T": T,
                "max_len": max_len, "block_size": bs,
                "occupancy": occupancy,
                "cache_len": int(lens[0]),
                "allocated_blocks": allocated,
                "paged_vs_dense_max_err": err,
                "dense_us": dense_us, "shim_us": shim_us,
                "paged_us": paged_us,
                # per-step K/V bytes on top of the persistent cache:
                # shim materializes the dense view; the paged kernel
                # streams exactly the blocks its tables reach (+ the T
                # scratch writes), so its column tracks allocated blocks
                "shim_transient_bytes": B * M * bs * kv_elem,
                "paged_transient_bytes": (blocks_touched * bs + B * T)
                * kv_elem,
                # the engine-level transient model the same geometry
                # yields (EngineStats.step_transient_tokens): native
                # streams scratch only, shim/fallback a dense view —
                # deterministic, so the CI regression gate pins it exactly
                "step_transient_tokens_native": B * T,
                "step_transient_tokens_shim": B * M * bs,
            })
    return out


def paged_decode_variants(*, B=2, Hq=4, Hkv=2, D=64, T=16,
                          max_len=512, window=64) -> list:
    """The two template-only paged decode groups — sliding-window
    (gemma3-style) and absorbed-MLA (deepseek-style) — native kernel vs
    the gather fallback those groups used before the template existed.

    Gated columns: the deterministic engine transient model
    (``step_transient_tokens_native`` = scratch writes only vs
    ``..._fallback`` = the gathered dense view — the regression gate pins
    both exactly AND that native < fallback in the same run), the parity
    ``native_vs_fallback_max_err``, and the CPU latency proxies
    (``native_us`` times the kernel in interpret mode, ``fallback_us``
    the gather+softmax jnp path; tolerance-gated separately, never
    cross-compared — interpret mode is not a speed claim)."""
    key = jax.random.PRNGKey(2)
    r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)
    tm = jnp.tril(jnp.ones((T, T), bool))
    out = []
    for bs in (16, 128):
        M = max_len // bs
        num_blocks = 1 + B * M
        lens = np.asarray([max_len // 3, max_len // 2], np.int64)[:B]
        table = np.zeros((B, M), np.int32)
        nxt = 1
        for b in range(B):
            for j in range(-(-int(lens[b] + T) // bs)):
                table[b, j] = nxt
                nxt += 1
        lens_j = jnp.asarray(lens, jnp.int32)
        table_j = jnp.asarray(table)
        depth = jnp.arange(T, dtype=jnp.int32) % 4
        q_pos = lens_j[:, None] + depth[None, :]

        # sliding-window group
        q = r(0, (B, T, Hq, D))
        pk, pv = r(1, (num_blocks, Hkv, bs, D)), r(2, (num_blocks, Hkv,
                                                       bs, D))
        tk, tv = r(3, (B, T, Hkv, D)), r(4, (B, T, Hkv, D))
        w = jnp.int32(window)
        kernel = lambda a: tree_attention_paged_windowed_bshd(
            a, pk, pv, tk, tv, tm, lens_j, table_j, q_pos, w,
            interpret=True)
        fallback = lambda a: tree_attention_paged_windowed_ref(
            a.transpose(0, 2, 1, 3), pk, pv, tk.transpose(0, 2, 1, 3),
            tv.transpose(0, 2, 1, 3), tm, lens_j, table_j, q_pos,
            w).transpose(0, 2, 1, 3)
        err = float(jnp.max(jnp.abs(kernel(q) - fallback(q))))
        out.append({
            "variant": "windowed", "block_size": bs, "B": B, "T": T,
            "window": window, "max_len": max_len,
            "native_vs_fallback_max_err": err,
            "native_us": _timeit(kernel, q),
            "fallback_us": _timeit(fallback, q),
            "step_transient_tokens_native": B * T,
            "step_transient_tokens_fallback": B * M * bs,
        })

        # absorbed-MLA group (reduced deepseek split: r=64, rd=16)
        rlat, rd = 64, 16
        ql, qr = r(5, (B, T, Hq, rlat)), r(6, (B, T, Hq, rd))
        pl_, pr_ = r(7, (num_blocks, bs, rlat)), r(8, (num_blocks, bs, rd))
        tl, trp = r(9, (B, T, rlat)), r(10, (B, T, rd))
        scale = 1.0 / float(np.sqrt(32 + rd))
        kernel = lambda a: mla_attention_paged_bshd(
            a, qr, pl_, pr_, tl, trp, tm, lens_j, table_j, scale=scale,
            interpret=True)
        fallback = lambda a: mla_attention_paged_ref(
            a, qr, pl_, pr_, tl, trp, tm, lens_j, table_j, scale=scale)
        err = float(jnp.max(jnp.abs(kernel(ql) - fallback(ql))))
        out.append({
            "variant": "mla", "block_size": bs, "B": B, "T": T,
            "window": 0, "max_len": max_len,
            "native_vs_fallback_max_err": err,
            "native_us": _timeit(kernel, ql),
            "fallback_us": _timeit(fallback, ql),
            "step_transient_tokens_native": B * T,
            "step_transient_tokens_fallback": B * M * bs,
        })
    return out


def serve_longprompt_bench(*, n_req=8, max_batch=4, max_new_tokens=24,
                           max_len=512, long_len=384) -> list:
    """Long-prompt ragged serve sweep on random-init weights (the gate
    job trains nothing): unchunked vs chunked prefill on the identical
    stream.  Returns JSON-able dicts keyed by ``name``; the regression
    gate pins ``ttft_ms``/``p99_itl_ms``/``us_per_tok`` per row.

    Geometry is deliberately prefill-dominated — chain speculation (small
    verify step) against 384-token long prompts (~15x the short-prompt
    mean), i.e. the regime where one monolithic join visibly stalls
    every active slot and chunking has a spike to flatten.  On a toy
    where a whole prefill costs about one decode step there is nothing
    to win (and chunking's per-chunk dispatch overhead shows instead)."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.core.trees import chain_tree
    from repro.models.model import init_params
    from repro.serving.engine import (PagedSpeculativeEngine, Request,
                                      SpeculativeEngine)

    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    dp = init_draft_params(jax.random.fold_in(rng, 1), cfg)
    tree = chain_tree(4)
    engines = [
        ("unchunked", SpeculativeEngine, {}),
        ("chunk64", SpeculativeEngine, {"prefill_chunk": 64}),
        ("chunk128", SpeculativeEngine, {"prefill_chunk": 128}),
        # fig3-style fractional pool: 0.5x the dense footprint — pool
        # array traffic per step tracks the pool size on this jnp path,
        # so the dense-equivalent pool would just benchmark pool copies
        ("paged_chunk64", PagedSpeculativeEngine,
         {"block_size": 16, "prefill_chunk": 64,
          "num_blocks": (max_batch * max_len // 2) // 16 + 1}),
    ]
    out = []
    for name, engine_cls, ekw in engines:
        rs = np.random.RandomState(0)          # identical stream per engine
        reqs = []
        for i in range(n_req):
            plen = long_len if i % 4 == 0 else int(rs.randint(16, 33))
            reqs.append(Request(
                prompt=rs.randint(0, cfg.vocab_size, plen).astype(np.int32),
                max_new_tokens=max_new_tokens))
        eng = engine_cls(params, dp, cfg, tree, max_len=max_len, **ekw)
        stats = eng.serve(reqs, max_batch=max_batch)
        out.append({
            "name": name,
            "n_req": n_req, "max_batch": max_batch,
            "long_prompt_len": long_len,
            "tok_per_s": stats.tokens_per_s,
            "us_per_tok": 1e6 / max(stats.tokens_per_s, 1e-9),
            "ttft_ms": stats.mean_ttft_s * 1e3,
            "p99_ttft_ms": stats.p99_ttft_s * 1e3,
            "p99_itl_ms": stats.p99_itl_s * 1e3,
            "prefill_chunks": stats.prefill_chunks,
        })
    return out


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)

    # flash attention
    B, Hq, Hkv, S, D = 1, 4, 2, 512, 64
    q, k, v = r(0, (B, Hq, S, D)), r(1, (B, Hkv, S, D)), r(2, (B, Hkv, S, D))
    o = flash_attention(q, k, v, interpret=True)
    err = float(jnp.max(jnp.abs(o - flash_attention_ref(q, k, v))))
    us = _timeit(lambda a, b, c: flash_attention_ref(a, b, c), q, k, v)
    rows.append(csv_row("kernel_flash_attention", us,
                        f"interpret_max_err={err:.2e};S={S}"))

    # tree attention
    T = 16
    tk, tv = r(3, (B, Hkv, T, D)), r(4, (B, Hkv, T, D))
    qt = r(5, (B, Hq, T, D))
    tm = jnp.tril(jnp.ones((T, T), bool))
    lens = jnp.array([S - T], jnp.int32)
    o = tree_attention(qt, k, v, tk, tv, tm, lens, bk=128, interpret=True)
    err = float(jnp.max(jnp.abs(
        o - tree_attention_ref(qt, k, v, tk, tv, tm, lens))))
    us = _timeit(lambda a: tree_attention_ref(a, k, v, tk, tv, tm, lens), qt)
    rows.append(csv_row("kernel_tree_attention", us,
                        f"interpret_max_err={err:.2e};T={T};S={S}"))

    # linear attention chunk
    H, dk, dv = 4, 64, 64
    ql, kl = r(6, (B, H, S, dk)), r(7, (B, H, S, dk))
    vl = r(8, (B, H, S, dv))
    w = -jnp.exp(r(9, (B, H, S, dk)) * 0.5)
    u = r(10, (H, dk)) * 0.1
    o = linear_attn_chunk(ql, kl, vl, w, u, chunk=64, interpret=True)
    err = float(jnp.max(jnp.abs(o - linear_attn_ref(ql, kl, vl, w, u))))
    us = _timeit(lambda a: linear_attn_ref(a, kl, vl, w, u), ql)
    rows.append(csv_row("kernel_linear_attn_chunk", us,
                        f"interpret_max_err={err:.2e};S={S}"))

    # dense vs shim vs paged tree attention, JSON artifact
    sweep = tree_attention_paged_sweep()
    for s in sweep:
        rows.append(csv_row(
            f"kernel_tree_attention_paged_bs{s['block_size']}"
            f"_occ{s['occupancy']:g}",
            s["paged_us"],
            f"paged_vs_dense_max_err={s['paged_vs_dense_max_err']:.2e};"
            f"allocated_blocks={s['allocated_blocks']};"
            f"shim_transient_bytes={s['shim_transient_bytes']};"
            f"paged_transient_bytes={s['paged_transient_bytes']}"))

    # windowed + MLA paged decode: native template kernels vs the gather
    # fallback they retired (gated: transient model + parity + latency)
    variants = paged_decode_variants()
    for s in variants:
        rows.append(csv_row(
            f"kernel_paged_{s['variant']}_bs{s['block_size']}",
            s["fallback_us"],
            f"native_vs_fallback_max_err={s['native_vs_fallback_max_err']:.2e};"
            f"step_transient_tokens_native={s['step_transient_tokens_native']};"
            f"step_transient_tokens_fallback="
            f"{s['step_transient_tokens_fallback']}"))

    # long-prompt serving: TTFT + p99 inter-token latency, unchunked vs
    # chunked prefill (gated columns — see module docstring)
    serve_rows = serve_longprompt_bench()
    for s in serve_rows:
        rows.append(csv_row(
            f"serve_longprompt_{s['name']}", s["us_per_tok"],
            f"tok_per_s={s['tok_per_s']:.2f};ttft_ms={s['ttft_ms']:.1f};"
            f"p99_ttft_ms={s['p99_ttft_ms']:.1f};"
            f"p99_itl_ms={s['p99_itl_ms']:.2f};"
            f"prefill_chunks={s['prefill_chunks']}"))

    os.makedirs(os.path.dirname(RESULTS_JSON), exist_ok=True)
    with open(RESULTS_JSON, "w") as f:
        json.dump({"tree_attention_paged_sweep": sweep,
                   "paged_decode_variants": variants,
                   "serve_longprompt": serve_rows, "csv_rows": rows},
                  f, indent=2)
    print(f"wrote {os.path.normpath(RESULTS_JSON)}", flush=True)
    return rows


if __name__ == "__main__":
    run()
