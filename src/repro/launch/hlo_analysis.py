"""Roofline-term extraction from compiled dry-run artifacts.

collective_bytes parses the (post-SPMD) HLO text and sums the RESULT sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (per-device bytes moved per op invocation; ops inside
while-loop bodies are counted once — a documented approximation).

Roofline terms (TPU v5e, per step):
  compute    = HLO_FLOPs_per_device / PEAK_FLOPS
  memory     = HLO_bytes_per_device / HBM_BW
  collective = collective_bytes_per_device / (ICI links x link BW)
"""
from __future__ import annotations

import re
from typing import Dict

from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "bf16[16,128]{1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes summed over all op instances."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        eq = s.find(" = ")
        if eq < 0:
            continue
        rhs = s[eq + 3:]
        for kind in _COLLECTIVES:
            # op name directly follows the result type, e.g.
            # "%ag = bf16[2,64]{1,0} all-gather(...)"
            m = re.match(r"^\(?[\w\[\]{},\s]*?\)?\s*" + kind + r"\(", rhs)
            if m or rhs.split("(")[0].strip().endswith(kind):
                lhs_type = rhs.split(kind)[0]
                out[kind] += _shape_bytes(lhs_type)
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, *, n_ici_links: int = 4):
    """Returns the three roofline times in seconds + the bottleneck."""
    t_compute = flops_per_device / PEAK_FLOPS_BF16
    t_memory = bytes_per_device / HBM_BW
    t_coll = coll_bytes_per_device / (n_ici_links * ICI_BW_PER_LINK)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    return terms


def analytic_min_bytes(cfg, shape, n_chips: int, model_shards: int) -> float:
    """Per-device HBM-traffic LOWER BOUND assuming TPU-ideal kernels (the
    Pallas flash/tree/linear kernels keep score matrices in VMEM):

      train:   3 param passes (fwd read, bwd read, grad write) in bf16 +
               AdamW state read/write (16B/param fp32) + param write +
               ~24 activation r/w passes of (tokens x d) per layer
      prefill: 1 param pass + 4 activation passes/layer + cache write
      decode:  1 param pass + full KV/state-cache read + tree activations
    """
    p_dev = cfg.n_params * 2 / model_shards            # bf16, data-replicated
    p_active_dev = cfg.n_active_params * 2 / model_shards
    data_shards = max(n_chips // model_shards, 1)
    tok_dev = shape.global_batch * shape.seq_len / data_shards
    act = tok_dev * cfg.d_model * 2                    # one (tokens, d) pass
    if shape.kind == "train":
        return (3 * p_dev + (cfg.n_params * 18 / model_shards)
                + 24 * act * cfg.n_layers / 8)
    if shape.kind == "prefill":
        cache_write = _cache_bytes_dev(cfg, shape, data_shards, model_shards)
        return p_active_dev + 4 * act * cfg.n_layers / 8 + cache_write
    # decode: weights + cache dominate
    cache = _cache_bytes_dev(cfg, shape, data_shards, model_shards)
    return p_active_dev + cache


def _cache_bytes_dev(cfg, shape, data_shards, model_shards) -> float:
    B, S = shape.global_batch, shape.seq_len
    per_tok = 0.0
    if cfg.block_kind == "rwkv6":
        H = cfg.n_heads
        hd = cfg.d_model // H
        return cfg.n_layers * B * (H * hd * hd * 4 + 2 * cfg.d_model * 2) \
            / data_shards
    if cfg.block_kind == "mamba2":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        H = d_in // s.head_dim
        state = cfg.n_layers * B * H * s.d_state * s.head_dim * 4
        attn_tok = 0.0
        if cfg.hybrid_layer_ids:
            n_inv = len(cfg.hybrid_layer_ids)
            attn_tok = n_inv * 2 * cfg.n_kv_heads * cfg.resolved_head_dim * 2
        return (state + B * S * attn_tok) / data_shards
    if cfg.mla:
        per_tok = cfg.n_layers * (cfg.mla.kv_lora_rank
                                  + cfg.mla.qk_rope_dim) * 2
    else:
        kv_shard = model_shards if (cfg.n_kv_heads % model_shards == 0) else 1
        per_tok = cfg.n_layers * 2 * cfg.n_kv_heads * \
            cfg.resolved_head_dim * 2 / kv_shard
        # sliding-window layers only read `window` tokens
        if any(w > 0 for w in cfg.window_pattern):
            n_local = sum(1 for i in range(cfg.n_layers)
                          if cfg.window_for_layer(i) > 0)
            w = max(cfg.window_pattern)
            frac = (cfg.n_layers - n_local) / cfg.n_layers + \
                n_local / cfg.n_layers * min(1.0, w / S)
            per_tok *= frac
    return B * S * per_tok / data_shards


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); decode shapes use
    the tree/chain token count as D per step."""
    n = cfg.n_active_params
    if shape.kind == "train":
        tok = shape.global_batch * shape.seq_len
        return 6.0 * n * tok
    if shape.kind == "prefill":
        tok = shape.global_batch * shape.seq_len
        return 2.0 * n * tok
    # decode: one speculative step over T tree tokens
    from repro.launch.specs import tree_for
    t = tree_for(cfg)
    tok = shape.global_batch * (t.size if t else 1)
    return 2.0 * n * tok
