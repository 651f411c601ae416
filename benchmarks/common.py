"""Shared benchmark substrate.

Trains (once, checkpointed under results/ckpt) the container-scale Vicuna
stand-in base model on the synthetic conversation corpus, plus the three
draft-model variants the paper compares (§5, §6):

  medusa   — sequentially-independent heads, 1-layer MLP, data loss
  hydra    — sequentially-dependent heads, 1-layer MLP, data loss  (§3)
  hydra++  — sequentially-dependent, 4-layer MLP, teacher-distillation
             loss, PrefixAttention                                  (§3.1)

Every benchmark reports CSV rows "name,us_per_call,derived" per run.py's
contract; `derived` carries the figure-specific metric (acceptance length,
tokens/s, MT-proxy score, ...).
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import DraftConfig
from repro.core.heads import init_draft_params
from repro.core.trees import TreeSpec, default_tree
from repro.data.synthetic import DataPipeline, MarkovSpec
from repro.models.model import init_params
from repro.runtime_env import use_compilation_cache
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.trainer import TrainConfig, train_base, train_heads

# every benchmark process shares the persistent compile cache
use_compilation_cache()

CKPT_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "ckpt")
FAST = os.environ.get("REPRO_BENCH_FAST", "1") == "1"

BASE_STEPS = 150 if FAST else 400
HEAD_STEPS = 200 if FAST else 600

DRAFT_VARIANTS = {
    "medusa": (DraftConfig(kind="medusa", n_heads=4, n_mlp_layers=1),
               "data"),
    "hydra": (DraftConfig(kind="hydra", n_heads=4, n_mlp_layers=1),
              "data"),
    "hydra++": (DraftConfig(kind="hydra", n_heads=4, n_mlp_layers=4,
                            prefix_attention=True), "distill"),
}


@lru_cache(maxsize=1)
def base_setup():
    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    spec = MarkovSpec(vocab_size=cfg.vocab_size, branch=4, peak=0.7, seed=0)
    pipe = DataPipeline(spec, seq_len=128, batch_size=16, n_train=256,
                        n_eval=32)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    path = os.path.join(CKPT_DIR, "base_tiny")
    if os.path.exists(os.path.join(path, "arrays.npz")):
        params = load_checkpoint(path, params)
    else:
        tc = TrainConfig(total_steps=BASE_STEPS, warmup=30, log_every=100)
        params, _ = train_base(params, cfg, tc, pipe.train_batches(
            BASE_STEPS))
        save_checkpoint(path, params)
    return cfg, params, pipe


def draft_setup(variant: str, *, steps: int | None = None,
                objective: str | None = None, noise_alpha: float = 0.0,
                tag: str | None = None):
    """Returns (cfg_with_draft, draft_params) — trained & checkpointed."""
    cfg, params, pipe = base_setup()
    dc, obj = DRAFT_VARIANTS[variant]
    objective = objective or obj
    steps = steps or HEAD_STEPS
    c2 = dataclasses.replace(cfg, draft=dc)
    rng = jax.random.PRNGKey(7)
    dp = init_draft_params(rng, c2)
    tag = tag or f"{variant}_{objective}" + (
        f"_noise{noise_alpha:g}" if noise_alpha else "")
    path = os.path.join(CKPT_DIR, f"heads_{tag}")
    if os.path.exists(os.path.join(path, "arrays.npz")):
        dp = load_checkpoint(path, dp)
    else:
        tc = TrainConfig(total_steps=steps, warmup=30, log_every=100)
        dp, _ = train_heads(dp, params, c2, tc, pipe.train_batches(steps),
                            objective=objective, noise_alpha=noise_alpha,
                            rng=rng)
        save_checkpoint(path, dp)
    return c2, dp


def eval_prompts(n: int, length: int = 32):
    _, _, pipe = base_setup()
    return jnp.asarray(pipe.eval_batch(n)[:, :length])


def timed_generate(params, dp, cfg, tree, prompts, *, max_new_tokens=48,
                   criterion="greedy", use_speculative=True, **kw):
    """Returns (tokens/s wall, tokens/step acceptance, steps)."""
    from repro.core.speculative import generate
    # warm-up/compile
    _ = generate(params, dp, cfg, tree, prompts, max_new_tokens=4,
                 max_len=512, criterion=criterion,
                 use_speculative=use_speculative, **kw)
    t0 = time.time()
    toks, steps, acc = generate(params, dp, cfg, tree, prompts,
                                max_new_tokens=max_new_tokens, max_len=512,
                                criterion=criterion,
                                use_speculative=use_speculative, **kw)
    wall = time.time() - t0
    B = prompts.shape[0]
    n_tokens = float(jnp.sum(jnp.asarray(acc))) if use_speculative else \
        steps * B
    return n_tokens / wall, float(jnp.mean(jnp.asarray(acc))), steps, toks


def ragged_requests(n: int, *, seed: int = 0, min_len: int = 16,
                    max_len: int = 32, max_new_tokens: int = 32,
                    long_every: int = 0, long_len: int = 0):
    """A ragged serving workload: n requests with mixed prompt lengths and
    mixed budgets drawn deterministically from `seed` (so the continuous
    and bucketed engines can be benchmarked on the identical stream).

    ``long_every=k`` makes every k-th request a long prompt of
    ``long_len`` tokens (>= 4x the stream mean) — the head-of-line
    workload whose p99 inter-token latency chunked prefill targets
    (DESIGN.md §8).  Long prompts wrap the eval rows to reach
    ``long_len``."""
    from repro.serving.engine import Request
    _, _, pipe = base_setup()
    rs = np.random.RandomState(seed)
    toks = np.asarray(pipe.eval_batch(n))
    reqs = []
    for i in range(n):
        plen = rs.randint(min_len, max_len + 1)
        if long_every and i % long_every == 0:
            plen = long_len or 4 * max_len
        row = np.resize(toks[i], plen)          # wrap past the eval width
        reqs.append(Request(
            prompt=row.astype(np.int32),
            max_new_tokens=int(rs.randint(max(max_new_tokens // 2, 2),
                                          max_new_tokens + 1))))
    return reqs


def timed_serve(engine_cls, params, dp, cfg, tree, requests, *,
                max_batch: int = 8, use_speculative: bool = True,
                criterion: str = "greedy", engine_kwargs: dict | None = None):
    """Serve `requests` through `engine_cls`; returns the EngineStats
    (tokens/s, slot utilization, per-request latency percentiles).
    `engine_kwargs` forwards paged-cache geometry (block_size/num_blocks)."""
    eng = engine_cls(params, dp, cfg, tree, max_len=512,
                     use_speculative=use_speculative, criterion=criterion,
                     **(engine_kwargs or {}))
    return eng.serve(requests, max_batch=max_batch)


def serve_derived(stats) -> str:
    """The figure-3 derived-metric string for one engine run.  The memory
    columns report cache positions: `kv_reserved_tok` is the persistent
    HBM reservation (dense: max_batch x max_len; paged: the block pool),
    `kv_peak_tok` the positions actually backed by blocks at the high-water
    mark, `oversub` the dense-equivalent / reserved ratio (> 1 means the
    pool oversubscribes the dense footprint), and `step_transient_tok`
    the positions one jitted step materializes ON TOP of the reservation
    (0 dense in-place; max_batch x T for the native paged kernel;
    max_batch x max_len when any layer takes the per-layer gather
    fallback — windowed groups, MLA — or under the shim oracle).

    Responsiveness columns (DESIGN.md §8): `ttft_ms`/`p99_ttft_ms` are
    queue-to-first-token latency (mean / p99 across requests), and
    `p99_itl_ms` the p99 inter-token gap across every served token — the
    column a monolithic long-prompt prefill blows up (every active slot
    stalls for the whole join) and chunked prefill repairs.  Chunked rows
    additionally carry `prefill_chunks`/`prefill_tok`.

    Host-overlap columns (the async serve loop, DESIGN.md §7):
    `host_stall_ms` is the wall time host bookkeeping STARVED the device
    pipeline (host working with no step in flight — the serialization
    double-buffering removes; ~0 for async rows, one harvest+join+
    dispatch interval per step for sync rows), `stall_frac` that as a
    fraction of serving wall-clock, `read_wait_ms` the separate
    device-bound time spent blocked inside device-to-host reads, and
    `inflight_peak` the deepest dispatched-unharvested window the loop
    reached (1 = synchronous, 2 = double-buffered)."""
    row = (f"tok_per_s={stats.tokens_per_s:.2f};"
           f"tok_per_step={stats.tokens_per_step:.3f};"
           f"slot_util={stats.slot_utilization:.3f};"
           f"mean_lat_ms={stats.mean_latency_s * 1e3:.1f};"
           f"p99_lat_ms={stats.p99_latency_s * 1e3:.1f};"
           f"ttft_ms={stats.mean_ttft_s * 1e3:.1f};"
           f"p99_ttft_ms={stats.p99_ttft_s * 1e3:.1f};"
           f"p99_itl_ms={stats.p99_itl_s * 1e3:.2f};"
           f"host_stall_ms={stats.host_stall_s * 1e3:.1f};"
           f"stall_frac={stats.host_stall_frac:.3f};"
           f"read_wait_ms={stats.read_wait_s * 1e3:.1f};"
           f"inflight_peak={stats.steps_in_flight}")
    if stats.prefill_chunks:
        row += (f";prefill_chunks={stats.prefill_chunks}"
                f";prefill_tok={stats.prefill_tokens}")
    if stats.pool_tokens:                    # paged engine: memory columns
        row += (f";kv_reserved_tok={stats.pool_tokens}"
                f";kv_peak_tok={stats.peak_pool_tokens}"
                f";blocks_in_use={stats.peak_blocks_in_use}/"
                f"{stats.num_blocks - 1}"
                f";oversub={1.0 / stats.kv_pool_frac:.2f}x"
                f";preempt={stats.preemptions}"
                f";step_transient_tok={stats.step_transient_tokens}")
    elif stats.dense_equiv_tokens:
        row += (f";kv_reserved_tok={stats.dense_equiv_tokens}"
                f";step_transient_tok=0")
    return row


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    row = f"{name},{us_per_call:.1f},{derived}"
    print(row, flush=True)
    return row


def quality_proxy_nll(params, cfg, tokens) -> float:
    """Base-model NLL of generated continuations — stands in for the
    paper's LLM-judge quality score (lower = more base-model-like)."""
    from repro.core.distill import lm_loss
    toks = jnp.asarray(np.maximum(np.asarray(tokens), 0))[:, :64]
    loss, m = lm_loss(params, cfg, toks)
    return float(m["nll"])
