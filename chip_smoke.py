"""Smoke run of the served path on one TPU chip.

Serves minitron-4b at its published widths (32 layers, d_model 3072, 24
query heads over 8 KV heads of 128, FFN 9216, vocabulary 256000) with
Hydra++ draft heads and random bf16 weights drawn from ``--seed``,
through ``PagedSpeculativeEngine`` with the native paged kernels and
chunked prefill, built by the same ``build_engine``/``serve`` that
``repro.launch.serve`` calls.  Phases, in order:

  device   JAX must find a TPU; anything else exits 1 with no result
  kernels  each paged attention-template instantiation (GQA, windowed
           GQA, absorbed MLA) against its ref.py oracle at serving
           widths, with ragged cache lengths and NULL block-table holes
  serve    8 seeded requests (prompts of 64-256 tokens, 32 new tokens
           each) at max_batch 4: every request gets exactly its budget of
           in-vocabulary tokens, and the compiled step holds Mosaic
           kernels (``tpu_custom_call``), not interpreted ones

The numbers printed are those of one smoke run, not a benchmark.  A
failed phase exits non-zero.  On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed N]

Everything runs in this one process, as a chip belongs to one process at
a time.  JAX's compile cache is kept where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.trees import default_tree  # noqa: E402
from repro.kernels.attention_template.ops import (  # noqa: E402
    mla_attention_paged_bshd, tree_attention_paged_windowed_bshd)
from repro.kernels.attention_template.ref import (  # noqa: E402
    mla_attention_paged_ref, tree_attention_paged_windowed_ref)
from repro.kernels.tree_attention.ops import (  # noqa: E402
    tree_attention_paged_bshd)
from repro.kernels.tree_attention.ref import (  # noqa: E402
    tree_attention_paged_ref)
from repro.launch.serve import build_engine, random_weights, serve  # noqa
from repro.runtime_env import use_compilation_cache  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

ARCH = "minitron-4b"
MLA_ARCH = "deepseek-v2-lite-16b"
MAX_BATCH, MAX_LEN, BLOCK_SIZE = 4, 512, 16
N_REQUESTS, PROMPT_LENS, NEW_TOKENS, PREFILL_CHUNK = 8, (64, 256), 32, 64
TREE_SIZE, WINDOW = 16, 64         # the kernel check's tree and window
# bf16 kernel output against a float32 oracle on the same bf16 inputs,
# outputs of order 1: a few bf16 ulps (2**-8 relative) of headroom
BF16_ATOL = 2e-2


def _fail(msg: str):
    raise SystemExit(f"[smoke] FAIL {msg}")


def _tables(rs, lens, T, bs, M, n_blocks):
    """Block tables covering ``lens[b] + T`` tokens per slot from a
    shuffled pool, with a NULL hole at block 1 of every slot that spans
    more than two blocks (the kernel must skip it, the oracle masks it)."""
    ids = rs.permutation(np.arange(1, n_blocks))
    table = np.zeros((len(lens), M), np.int32)
    used = 0
    for b, n in enumerate(lens):
        k = -(-(int(n) + T) // bs)
        table[b, :k] = ids[used:used + k]
        used += k
        if k > 2:
            table[b, 1] = 0
    return table


def check_kernels(seed: int) -> None:
    """Each paged instantiation against its oracle, at the widths of
    ``ARCH`` (GQA) and ``MLA_ARCH`` (absorbed MLA)."""
    cfg, mcfg = get_config(ARCH), get_config(MLA_ARCH)
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    H, r, rd = mcfg.n_heads, mcfg.mla.kv_lora_rank, mcfg.mla.qk_rope_dim
    B, T, bs, max_len = MAX_BATCH, TREE_SIZE, BLOCK_SIZE, MAX_LEN
    M = max_len // bs
    N = 1 + B * M
    rs = np.random.RandomState(seed)
    # ragged: one near-empty slot, one near-full, the rest anywhere
    lens = rs.randint(0, max_len - T + 1, B)
    lens[0], lens[-1] = 3, max_len - T - 5
    table = jnp.asarray(_tables(rs, lens, T, bs, M, N))
    tree = default_tree(T, 4, 4)
    tm = jnp.asarray(tree.ancestor_mask)
    cache_len = jnp.asarray(lens, jnp.int32)
    q_pos = cache_len[:, None] + jnp.asarray(tree.depth, jnp.int32)[None]
    w = jnp.int32(WINDOW)

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    draw = lambda *shape: jax.random.normal(next(keys), shape, jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)
    kl = lambda x: f32(x).transpose(0, 2, 1, 3)       # model -> kernel layout

    q, tk, tv = draw(B, T, Hq, D), draw(B, T, Hkv, D), draw(B, T, Hkv, D)
    pk, pv = draw(N, Hkv, bs, D), draw(N, Hkv, bs, D)
    ql, qr = draw(B, T, H, r), draw(B, T, H, rd)
    pl_, pr_ = draw(N, bs, r), draw(N, bs, rd)
    tl, trp = draw(B, T, r), draw(B, T, rd)
    scale = 1.0 / (mcfg.mla.qk_nope_dim + rd) ** 0.5

    cases = {
        "gqa_paged": (
            lambda: tree_attention_paged_bshd(q, pk, pv, tk, tv, tm,
                                              cache_len, table),
            lambda: tree_attention_paged_ref(
                kl(q), f32(pk), f32(pv), kl(tk), kl(tv), tm, cache_len,
                table).transpose(0, 2, 1, 3)),
        "gqa_paged_windowed": (
            lambda: tree_attention_paged_windowed_bshd(
                q, pk, pv, tk, tv, tm, cache_len, table, q_pos, w),
            lambda: tree_attention_paged_windowed_ref(
                kl(q), f32(pk), f32(pv), kl(tk), kl(tv), tm, cache_len,
                table, q_pos, w).transpose(0, 2, 1, 3)),
        "mla_paged": (
            lambda: mla_attention_paged_bshd(ql, qr, pl_, pr_, tl, trp, tm,
                                             cache_len, table, scale=scale),
            lambda: mla_attention_paged_ref(
                f32(ql), f32(qr), f32(pl_), f32(pr_), f32(tl), f32(trp), tm,
                cache_len, table, scale=scale)),
    }
    print(f"[smoke] kernels: batch={B} tree={T} block_size={bs} "
          f"max_len={max_len} cache_len={lens.tolist()} "
          f"gqa=(Hq={Hq}, Hkv={Hkv}, D={D}) mla=(H={H}, r={r}, rd={rd}) "
          f"window={WINDOW} tol={BF16_ATOL}")
    for name, (kernel, oracle) in cases.items():
        out = f32(kernel())
        with jax.default_matmul_precision("highest"):
            ref = oracle()
        if out.shape != ref.shape:
            _fail(f"{name}: shape {out.shape} != oracle {ref.shape}")
        err = float(jnp.max(jnp.abs(out - ref)))
        finite = bool(jnp.all(jnp.isfinite(out)))
        print(f"[smoke] kernel {name} shape={tuple(out.shape)} "
              f"max_abs_err={err!r} finite={finite}")
        if not finite or not err <= BF16_ATOL:
            _fail(f"{name}: max abs error {err!r} > {BF16_ATOL} "
                  f"or non-finite output")


def serve_smoke(cfg, seed: int):
    """Serve seeded requests through the paged engine and check every
    output.  Returns the text of the compiled decode step."""
    t0 = time.time()
    params, dp = random_weights(cfg, seed)
    jax.block_until_ready((params, dp))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves((params, dp)))
    print(f"[smoke] init {cfg.name}: {n_bytes} bytes of {cfg.dtype} "
          f"weights in {time.time() - t0!r} s")

    eng = build_engine(cfg, params, dp, engine="paged", max_batch=MAX_BATCH,
                       max_len=MAX_LEN, block_size=BLOCK_SIZE,
                       prefill_chunk=PREFILL_CHUNK)
    rs = np.random.RandomState(seed)
    lo, hi = PROMPT_LENS
    reqs = [Request(prompt=rs.randint(0, cfg.vocab_size,
                                      rs.randint(lo, hi + 1)).astype(np.int32),
                    max_new_tokens=NEW_TOKENS) for _ in range(N_REQUESTS)]
    stats = serve(eng, reqs, max_batch=MAX_BATCH)

    for i, r in enumerate(reqs):
        out = np.asarray(r.output)
        if len(out) != NEW_TOKENS:
            _fail(f"request {i} got {len(out)} tokens, budget {NEW_TOKENS}")
        if out.min() < 0 or out.max() >= cfg.vocab_size:
            _fail(f"request {i} has tokens outside [0, {cfg.vocab_size})")
    print(f"[smoke] served {len(reqs)} requests, prompts "
          f"{sorted(len(r.prompt) for r in reqs)}, each {NEW_TOKENS} "
          f"in-vocabulary tokens")
    print(f"[smoke] smoke-run numbers (one run, not a benchmark): "
          f"steps={stats.steps} tokens={stats.tokens} "
          f"tokens_per_step={stats.tokens_per_step!r} "
          f"wall_s={stats.wall_s!r} compile_s={stats.warmup_s!r} "
          f"prefill_chunks={stats.prefill_chunks} "
          f"preemptions={stats.preemptions} "
          f"peak_blocks={stats.peak_blocks_in_use}/{stats.num_blocks - 1}")
    return eng.lower_step(MAX_BATCH).compile().as_text()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache = use_compilation_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found platform={dev.platform} "
              f"kind={dev.device_kind} count={len(devices)}")
        sys.exit(1)
    print(f"[smoke] device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} compile_cache={cache}")

    check_kernels(args.seed)
    text = serve_smoke(get_config(ARCH), args.seed)
    n_kernels = text.count("tpu_custom_call")
    print(f"[smoke] compiled step: {n_kernels} tpu_custom_call sites")
    if not n_kernels:
        _fail("the served step holds no Mosaic kernel (tpu_custom_call)")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] smoke-run peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
