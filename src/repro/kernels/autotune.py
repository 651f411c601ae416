"""Block-size autotuner for the attention template (DESIGN.md §11).

Times candidate block-size grids per (variant, backend, head-dim[,
allocator block_size]) and records the winners in
``results/autotune.<backend>.json``, which ``tuned_block_sizes``
(``repro.kernels``) consults at trace time.  Tunables per variant:

* ``flash``                — ``(bq, bk)`` tile grid of the self family;
* ``tree_dense``           — cache strip ``bk`` + the tree-axis ``pad_to``
  (the padded T is the tree family's "query block");
* ``tree_paged`` / ``tree_paged_windowed`` / ``mla_paged`` — ``pad_to``:
  the page is pinned to the allocator's ``block_size``, which therefore
  joins the cache key; the two page-walking variants also sweep their
  pages per compute block ``ppb``, sized in tokens (``candidates``).

CLI (also the CI surface — the nightly sweeps and checks, pushes stay on
the committed cache):

    python -m repro.kernels.autotune sweep [--out FILE] [--keys K ...]
    python -m repro.kernels.autotune check [--cache FILE]

``sweep`` times every candidate for every required key (default: the
keys the in-suite configs need, see ``required_keys``) and writes the
winner table.  ``check`` exits non-zero if the committed cache is
missing any required key — the guard against silently falling through
to untuned defaults.

Timing notes: on CPU the kernels run in interpret mode, so the sweep
measures the interpret path — a PROXY ordering, deterministic and cheap,
exactly like the repo's other CPU-side benchmarks; a TPU run of the same
CLI produces ``autotune.tpu.json`` with compiled-kernel timings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (autotune_cache_path, block_size_key,
                           resolve_backend)

# candidate grids — every entry must be legal for the sweep shapes below
CANDIDATES = {
    "flash": [{"bq": bq, "bk": bk} for bq in (64, 128, 256)
              for bk in (64, 128, 256)],
    "tree_dense": [{"pad_to": p, "bk": bk} for p in (8, 32)
                   for bk in (128, 256, 512)],
    "tree_paged": [{"pad_to": p} for p in (8, 16, 32)],
    "tree_paged_windowed": [{"pad_to": p} for p in (8, 16, 32)],
    "mla_paged": [{"pad_to": p} for p in (8, 16, 32)],
}

# tokens per compute block of the paged walk: ppb = tokens // block_size
_WALK_BLOCK_TOKENS = (128, 256)


def candidates(variant: str, block_size: int | None = None) -> list[dict]:
    """The candidate grid of one key.  The page-walking variants add
    ``ppb`` to each ``pad_to``, from block sizes in tokens, so that every
    allocator block size gets blocks of about the same tokens."""
    cands = CANDIDATES[variant]
    if variant not in ("tree_paged", "tree_paged_windowed"):
        return cands
    ppbs = sorted({max(1, t // (block_size or 16))
                   for t in _WALK_BLOCK_TOKENS})
    return [dict(c, ppb=k) for c in cands for k in ppbs]


# sweep workload (modest: the CPU interpret path is the common case)
_B, _HQ, _HKV, _T, _S = 2, 4, 2, 13, 512
_WARMUP, _REPS = 1, 3


def _rand(key, i, shape):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def _time(fn) -> float:
    """Best-of-N wall time in microseconds (after warmup)."""
    for _ in range(_WARMUP):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _cover_tables(lens, T, bs, M, num_blocks):
    table = np.zeros((_B, M), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for j in range(-(-(int(L) + T) // bs)):
            table[b, j] = nxt
            nxt += 1
    assert nxt <= num_blocks
    return jnp.asarray(table)


def _bench_fn(variant: str, head_dim: int, block_size: int | None,
              cand: dict):
    """Build a nullary closure running one kernel call for ``cand``."""
    key = jax.random.PRNGKey(0)
    D = head_dim
    if variant == "flash":
        from repro.kernels.flash_attention.kernel import flash_attention
        q = _rand(key, 0, (_B, _HQ, _S, D))
        k = _rand(key, 1, (_B, _HKV, _S, D))
        v = _rand(key, 2, (_B, _HKV, _S, D))
        return lambda: flash_attention(q, k, v, window=64, **cand)

    lens = jnp.asarray([_S // 3, _S - _T], jnp.int32)
    tm = jnp.tril(jnp.ones((_T, _T), bool))
    depth = jnp.arange(_T, dtype=jnp.int32) % 4
    q_pos = lens[:, None] + depth[None, :]

    if variant == "tree_dense":
        from repro.kernels.tree_attention.ops import tree_attention_bshd
        q = _rand(key, 0, (_B, _T, _HQ, D))
        ck = _rand(key, 1, (_B, _S, _HKV, D))
        cv = _rand(key, 2, (_B, _S, _HKV, D))
        tk = _rand(key, 3, (_B, _T, _HKV, D))
        tv = _rand(key, 4, (_B, _T, _HKV, D))
        return lambda: tree_attention_bshd(q, ck, cv, tk, tv, tm, lens,
                                           **cand)

    bs = block_size or 16
    M = -(-(_S + _T) // bs)
    N = 2 * M + 2
    table = _cover_tables([int(x) for x in lens], _T, bs, M, N)
    if variant in ("tree_paged", "tree_paged_windowed"):
        pk = _rand(key, 1, (N, _HKV, bs, D))
        pv = _rand(key, 2, (N, _HKV, bs, D))
        q = _rand(key, 0, (_B, _T, _HQ, D))
        tk = _rand(key, 3, (_B, _T, _HKV, D))
        tv = _rand(key, 4, (_B, _T, _HKV, D))
        if variant == "tree_paged":
            from repro.kernels.tree_attention.ops import (
                tree_attention_paged_bshd)
            return lambda: tree_attention_paged_bshd(
                q, pk, pv, tk, tv, tm, lens, table, **cand)
        from repro.kernels.attention_template.ops import (
            tree_attention_paged_windowed_bshd)
        w = jnp.int32(64)
        return lambda: tree_attention_paged_windowed_bshd(
            q, pk, pv, tk, tv, tm, lens, table, q_pos, w, **cand)

    if variant == "mla_paged":
        from repro.kernels.attention_template.ops import (
            mla_attention_paged_bshd)
        # head_dim keys the cache as r + rd; sweep with the repo's
        # reduced-MLA split (r = hd - 16, rd = 16)
        rd = 16
        r = D - rd
        pl_ = _rand(key, 1, (N, bs, r))
        pr_ = _rand(key, 2, (N, bs, rd))
        ql = _rand(key, 0, (_B, _T, _HQ, r))
        qr = _rand(key, 3, (_B, _T, _HQ, rd))
        tl = _rand(key, 4, (_B, _T, r))
        trp = _rand(key, 5, (_B, _T, rd))
        scale = 1.0 / float(np.sqrt(D))
        return lambda: mla_attention_paged_bshd(
            ql, qr, pl_, pr_, tl, trp, tm, lens, table, scale=scale, **cand)

    raise ValueError(f"unknown autotune variant {variant!r}")


def sweep_entry(variant: str, head_dim: int,
                block_size: int | None = None) -> dict:
    """Time every candidate for one key; return the winner entry
    (winning sizes + the full candidate->us table)."""
    results = {}
    for cand in candidates(variant, block_size):
        label = "x".join(str(v) for v in cand.values())
        results[label] = (_time(_bench_fn(variant, head_dim, block_size,
                                          cand)), cand)
    best_label = min(results, key=lambda c: results[c][0])
    entry = dict(results[best_label][1])
    entry["sweep_us"] = {c: round(us, 1) for c, (us, _) in results.items()}
    return entry


# ---------------------------------------------------------------------------
# required keys: what the in-suite configs resolve at trace time
# ---------------------------------------------------------------------------

# kernel/test-level shapes exercised directly by the suite and benches
_SUITE_KEYS = [
    ("flash", 64, None),
    ("tree_dense", 64, None),
    ("tree_paged", 64, 16),
    ("tree_paged", 64, 128),
    ("tree_paged_windowed", 64, 16),
    ("tree_paged_windowed", 64, 128),
    ("mla_paged", 80, 16),
    ("mla_paged", 80, 128),
]


def required_keys() -> list[tuple[str, int, int | None]]:
    """Every (variant, head_dim, block_size) the in-suite configs can
    resolve at trace time: the reduced() smoke variants of every
    registered config on the paged engine's default block size, plus the
    kernel-level suite shapes."""
    from repro.configs import get_config, list_configs
    keys = list(_SUITE_KEYS)
    for name in list_configs():
        cfg = get_config(name).reduced()
        if cfg.block_kind != "attn" and not cfg.hybrid_layer_ids:
            continue     # pure-SSM stacks never touch the attention paths
        windowed = any(w > 0 for w in cfg.window_pattern)
        for bs in (16,):                      # paged-engine test default
            if cfg.mla is not None:
                hd = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
                keys.append(("mla_paged", hd, bs))
            else:
                hd = cfg.resolved_head_dim
                if cfg.hybrid_layer_ids:    # the pool's lane-padded width
                    hd = -(-hd // 128) * 128
                keys.append(("tree_paged", hd, bs))
                if windowed:
                    keys.append(("tree_paged_windowed", hd, bs))
        if cfg.mla is None:
            keys.append(("flash", cfg.resolved_head_dim, None))
            keys.append(("tree_dense", cfg.resolved_head_dim, None))
    seen, out = set(), []
    for k in keys:
        if k not in seen:
            seen.add(k)
            out.append(k)
    return out


def _sweep_main(args) -> int:
    backend = resolve_backend()
    path = args.out or autotune_cache_path(backend)
    keys = required_keys()
    if args.keys:
        want = set(args.keys)
        keys = [k for k in keys if block_size_key(*k) in want]
    entries = {}
    for variant, hd, bs in keys:
        key = block_size_key(variant, hd, bs)
        entries[key] = sweep_entry(variant, hd, block_size=bs)
        winner = {k: v for k, v in entries[key].items() if k != "sweep_us"}
        print(f"{key}: winner {winner}", flush=True)
    payload = {"format": 1, "backend": backend, "jax": jax.__version__,
               "entries": entries}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(entries)} entries -> {path}")
    return 0


def _check_main(args) -> int:
    path = args.cache or autotune_cache_path()
    try:
        with open(path) as f:
            entries = json.load(f).get("entries", {})
    except (OSError, ValueError) as e:
        print(f"FAIL: cannot read winner cache {path}: {e}")
        return 1
    missing = [block_size_key(*k) for k in required_keys()
               if block_size_key(*k) not in entries]
    if missing:
        print(f"FAIL: {path} is missing {len(missing)} required "
              "winner entries (in-suite configs would silently fall "
              "through to untuned defaults):")
        for key in missing:
            print(f"  {key}")
        return 1
    print(f"OK: {path} covers all {len(required_keys())} required keys")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sp = sub.add_parser("sweep", help="time candidates, write winner cache")
    sp.add_argument("--out", help="output path (default: the backend's "
                    "committed cache location)")
    sp.add_argument("--keys", nargs="*",
                    help="restrict to these cache keys")
    cp = sub.add_parser("check", help="fail if the cache misses a "
                        "required key")
    cp.add_argument("--cache", help="cache path to check (default: the "
                    "backend's committed cache)")
    args = ap.parse_args(argv)
    return _sweep_main(args) if args.cmd == "sweep" else _check_main(args)


if __name__ == "__main__":
    sys.exit(main())
