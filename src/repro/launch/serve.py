"""Serving launcher: one speculative-decoding service per arch.

By default it serves the arch's ``reduced()`` config in float32 with
random weights, a CPU-sized smoke.  ``--full-config`` serves the
published widths in the config's dtype on one device, unsharded: there
is no mesh here.  minitron-4b fits one TPU v5e this way, and
``chip_smoke.py`` drives that path through the same ``build_engine`` and
``serve`` that ``main`` calls.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.heads import init_draft_params
from repro.launch.specs import tree_for
from repro.models.model import init_params
from repro.runtime_env import use_compilation_cache
from repro.serving.engine import (BucketedEngine, EngineStats,
                                  PagedSpeculativeEngine, Request,
                                  SpeculativeEngine)


def random_weights(cfg: ModelConfig, seed: int = 0):
    """Base and draft-head parameters drawn from ``seed``."""
    rng = jax.random.PRNGKey(seed)
    return (init_params(rng, cfg),
            init_draft_params(jax.random.fold_in(rng, 1), cfg))


def build_engine(cfg: ModelConfig, params, draft_params, *,
                 engine: str = "continuous", max_batch: int = 2,
                 max_len: int = 512, prefill_chunk: int = 0,
                 prefill_budget: int = 0, sync: bool = False,
                 block_size: int = 16, pool_frac: float = 0.5):
    """The engine ``serve`` drives, with the launcher's defaults.  The
    paged engine's pool holds ``pool_frac`` of the dense
    ``max_batch × max_len`` footprint (DESIGN.md §6)."""
    tree = tree_for(cfg)
    inflight = 1 if sync else 2
    chunk_kw = {}
    if prefill_chunk and engine != "bucketed":
        chunk_kw = {"prefill_chunk": prefill_chunk,
                    "prefill_budget": prefill_budget or None}
    if engine == "paged":
        usable = max(int(pool_frac * max_batch * max_len) // block_size, 4)
        return PagedSpeculativeEngine(params, draft_params, cfg, tree,
                                      max_len=max_len, block_size=block_size,
                                      num_blocks=usable + 1,
                                      inflight=inflight, **chunk_kw)
    if engine == "continuous":
        return SpeculativeEngine(params, draft_params, cfg, tree,
                                 max_len=max_len, inflight=inflight,
                                 **chunk_kw)
    return BucketedEngine(params, draft_params, cfg, tree, max_len=max_len)


def serve(eng, requests: List[Request], *, max_batch: int,
          stream: bool = False) -> EngineStats:
    """Serve ``requests`` to completion and return the engine's stats.
    ``stream`` feeds half of them through the live queue (``submit()`` up
    front, the rest from a generator source the loop pulls as slots free
    up) instead of a pre-collected list; the bucketed engine has no live
    queue and ignores it."""
    if stream and not isinstance(eng, BucketedEngine):
        split = max(len(requests) // 2, 1)
        for r in requests[:split]:
            eng.submit(r)
        return eng.serve(source=iter(requests[split:]), max_batch=max_batch)
    return eng.serve(requests, max_batch=max_batch)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=2,
                    help="slot-pool size (max_batch)")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths in [prompt-len/2, prompt-len]")
    ap.add_argument("--long-prompts", action="store_true",
                    help="make every 4th request a long prompt (4x "
                         "prompt-len, i.e. >= 4x the stream mean) — the "
                         "head-of-line workload chunked prefill exists "
                         "for (DESIGN.md §8)")
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: split every prompt into "
                         "fixed-size chunks the scheduler interleaves "
                         "with decode steps (0 = monolithic join; "
                         "continuous/paged engines only)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prompt tokens co-scheduled per decode step "
                         "(default: one chunk)")
    ap.add_argument("--engine", choices=("continuous", "paged", "bucketed"),
                    default="continuous")
    ap.add_argument("--sync", action="store_true",
                    help="disable the double-buffered host loop "
                         "(inflight=1; continuous/paged engines only)")
    ap.add_argument("--stream", action="store_true",
                    help="feed requests through the live-queue API "
                         "(submit() + a generator source) instead of a "
                         "pre-collected list")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--pool-frac", type=float, default=0.5,
                    help="paged engine: block-pool size as a fraction of "
                         "the dense max_batch x max_len footprint "
                         "(DESIGN.md §6)")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths in the config's dtype "
                         "(one device, unsharded)")
    args = ap.parse_args()
    use_compilation_cache()

    cfg = get_config(args.arch)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode service "
                         "(DESIGN.md §4)")
    if not args.full_config:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")

    params, dp = random_weights(cfg)
    eng = build_engine(cfg, params, dp, engine=args.engine,
                       max_batch=args.batch,
                       prefill_chunk=args.prefill_chunk,
                       prefill_budget=args.prefill_budget, sync=args.sync,
                       block_size=args.block_size, pool_frac=args.pool_frac)
    tree = eng.tree
    print(f"[serve] arch={cfg.name} tree={tree.size} "
          f"(chain={tree.max_depth + 1 == tree.size})")
    rs = np.random.RandomState(0)
    reqs = []
    for i in range(args.requests or args.batch):
        plen = (rs.randint(max(args.prompt_len // 2, 1), args.prompt_len + 1)
                if args.ragged else args.prompt_len)
        if args.long_prompts and i % 4 == 0:
            plen = 4 * args.prompt_len
        reqs.append(Request(
            prompt=rs.randint(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new_tokens))
    stats = serve(eng, reqs, max_batch=args.batch, stream=args.stream)
    print(f"[serve] engine={args.engine} steps={stats.steps} "
          f"tokens={stats.tokens} tok/step={stats.tokens_per_step:.2f} "
          f"tok/s={stats.tokens_per_s:.1f} "
          f"util={stats.slot_utilization:.3f} "
          f"mean_lat={stats.mean_latency_s * 1e3:.1f}ms "
          f"p99_lat={stats.p99_latency_s * 1e3:.1f}ms "
          f"ttft={stats.mean_ttft_s * 1e3:.1f}ms "
          f"p99_itl={stats.p99_itl_s * 1e3:.1f}ms "
          f"host_stall={stats.host_stall_s * 1e3:.1f}ms "
          f"({stats.host_stall_frac:.0%} of wall) "
          f"read_wait={stats.read_wait_s * 1e3:.1f}ms "
          f"inflight_peak={stats.steps_in_flight}")
    if stats.prefill_chunks:
        print(f"[serve] chunked prefill: chunk={eng.prefill_chunk} "
              f"budget={eng.prefill_budget} chunks={stats.prefill_chunks} "
              f"prompt_tokens={stats.prefill_tokens}")
    if stats.pool_tokens:
        print(f"[serve] paged KV: pool={stats.pool_tokens} tok "
              f"(dense equivalent {stats.dense_equiv_tokens} tok, "
              f"{1.0 / stats.kv_pool_frac:.1f}x oversubscribed) "
              f"peak_blocks={stats.peak_blocks_in_use}/"
              f"{stats.num_blocks - 1} preemptions={stats.preemptions}")


if __name__ == "__main__":
    main()
