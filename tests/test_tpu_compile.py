"""Compile the paged attention-template kernels for a TPU v5e that is
described, not attached (``interpret=False``).

These are the verify kernels of ``PagedSpeculativeEngine`` at the widths
it serves: minitron-4b's GQA (Hq=24 over Hkv=8 heads of 128, bf16) plain
and sliding-window, DeepSeek-V2-Lite's absorbed MLA (r=512, rd=64), and
zamba2-7b's shared-block attention (32 heads of 224, stored as 256 lanes)
with its Mamba2 chain kernels (``ssd_chain_verify``, ``ssd_chain_commit``)
and its chunk path; and the draft heads of both, which must pick their
tree children without a sort.
Mosaic refuses tiles and ops here that the interpreter accepts, so each
test asserts that the program holds exactly one ``tpu_custom_call`` — the
kernel was compiled, not interpreted, as one call — named after the jitted
``tree_attention_template`` as the chip benchmark's trace reader expects
(``bench/work/tree_attn.py::OP_PATTERN``).  Two tests check a refusal
instead: the reason absorbed MLA does not walk its pages, and the reason
the hybrid blocks' pool stores 224-wide heads padded to 256 lanes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles, as
an entry for a described chip cannot be read back without one.
"""
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention_template.ops import (
    mla_attention_paged_bshd, tree_attention_paged_windowed_bshd)
from repro.kernels.tree_attention.ops import tree_attention_paged_bshd

B, T, M = 4, 16, 32          # max_batch, tree size, blocks per slot
HQ, HKV, D = 24, 8, 128      # minitron-4b attention widths
MLA_H, MLA_R, MLA_RD = 16, 512, 64   # deepseek-v2-lite latent widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.compilation_cache.reset_cache()


def _compile_text(fn, args, sharding) -> str:
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


def _op_pattern() -> str:
    root = Path(__file__).resolve().parents[1]
    path = root / "bench" / "work" / "tree_attn.py"
    spec = importlib.util.spec_from_file_location("bench_tree_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.OP_PATTERN


def _assert_one_named_kernel(text: str, pattern: str | None = None) -> None:
    """One Mosaic kernel, its HLO instruction named as the benchmark's
    trace reader finds it (``%tree_attention_template.N = ...``)."""
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert re.match(pattern or _op_pattern(), calls[0]), calls[0][:120]


N = 1 + B * M                # pool blocks, the NULL block included
# cache_len and block_table, the last two operands of every instantiation
TABLE = [((B,), jnp.int32), ((B, M), jnp.int32)]


@pytest.mark.parametrize("bs", [16, 128])
def test_gqa_paged_compiles(one_chip, bs):
    bf = jnp.bfloat16
    args = [((B, T, HQ, D), bf), ((N, HKV, bs, D), bf), ((N, HKV, bs, D), bf),
            ((B, T, HKV, D), bf), ((B, T, HKV, D), bf), ((T, T), jnp.bool_),
            *TABLE]
    text = _compile_text(
        lambda *a: tree_attention_paged_bshd(*a, interpret=False), args,
        one_chip)
    _assert_one_named_kernel(text)


def test_gqa_paged_windowed_compiles(one_chip):
    bs = 16
    bf = jnp.bfloat16
    args = [((B, T, HQ, D), bf), ((N, HKV, bs, D), bf), ((N, HKV, bs, D), bf),
            ((B, T, HKV, D), bf), ((B, T, HKV, D), bf), ((T, T), jnp.bool_),
            *TABLE, ((B, T), jnp.int32), ((), jnp.int32)]
    text = _compile_text(
        lambda *a: tree_attention_paged_windowed_bshd(*a, interpret=False),
        args, one_chip)
    _assert_one_named_kernel(text)


def test_mla_paged_compiles(one_chip):
    # absorbed MLA stays on the per-entry grid (B, Hq, M + 1): Mosaic will
    # not slice a page of the 64-wide RoPE pool in HBM for a manual copy
    bs = 16
    bf = jnp.bfloat16
    args = [((B, T, MLA_H, MLA_R), bf), ((B, T, MLA_H, MLA_RD), bf),
            ((N, bs, MLA_R), bf), ((N, bs, MLA_RD), bf),
            ((B, T, MLA_R), bf), ((B, T, MLA_RD), bf), ((T, T), jnp.bool_),
            *TABLE]
    scale = 1.0 / (128 + MLA_RD) ** 0.5
    text = _compile_text(
        lambda *a: mla_attention_paged_bshd(*a, scale=scale, interpret=False),
        args, one_chip)
    _assert_one_named_kernel(text)


def test_mla_page_walk_is_refused(one_chip, monkeypatch):
    """Why absorbed MLA keeps the per-entry grid: the page walk copies
    each page by hand out of the pools in HBM, and Mosaic refuses that
    copy from the 64-wide RoPE pool.  Once this compiles, MLA can walk
    its pages too (``_walks_pages``)."""
    from repro.kernels.attention_template import kernel
    monkeypatch.setattr(kernel, "_walks_pages",
                        lambda spec: spec.layout == "paged")
    bs, b = 16, B // 2       # a batch of its own: no cached trace is reused
    bf = jnp.bfloat16
    args = [((b, T, MLA_H, MLA_R), bf), ((b, T, MLA_H, MLA_RD), bf),
            ((N, bs, MLA_R), bf), ((N, bs, MLA_RD), bf),
            ((b, T, MLA_R), bf), ((b, T, MLA_RD), bf), ((T, T), jnp.bool_),
            ((b,), jnp.int32), ((b, M), jnp.int32)]
    scale = 1.0 / (128 + MLA_RD) ** 0.5
    refusal = r"aligned to tiling \(128\), but is 64"
    with pytest.raises(Exception, match=refusal):
        _compile_text(
            lambda *a: mla_attention_paged_bshd(*a, scale=scale,
                                                interpret=False),
            args, one_chip)


# zamba2-7b: the verify step's Mamba2 chain and shared-block attention
ZB, ZT, ZM = 16, 5, 64       # max_batch, chain tokens, blocks per slot
ZH, ZD, ZP = 32, 224, 256    # heads, head width, stored lanes


def test_ssd_chain_verify_compiles(one_chip):
    from repro.kernels.ssd_chain.ops import ssd_chain_verify_bthd
    G, ds, H, hd = 2, 64, 112, 64
    bf, f32 = jnp.bfloat16, jnp.float32
    args = [((ZB, ZT, G, ds), bf), ((ZB, ZT, G, ds), bf),
            ((ZB, ZT, H, hd), bf), ((ZB, ZT, H), f32), ((H,), f32),
            ((ZB, ds, H * hd), f32)]
    text = _compile_text(
        lambda *a: ssd_chain_verify_bthd(*a, interpret=False), args,
        one_chip)
    _assert_one_named_kernel(text, r"^%ssd_chain_verify(\.\d+)? ")


def test_ssd_chain_commit_compiles(one_chip):
    """The commit's replay over a stack of 6 layers of 16 rows."""
    from repro.kernels.ssd_chain.ops import ssd_chain_commit_rows
    L, G, ds, HP = 6, 2, 64, 112 * 64
    f32 = jnp.float32
    args = [((L, ZB, ZT, HP), f32), ((L, ZB, ZT, HP), f32),
            ((L, ZB, G, ZT, ds, 1), f32), ((L, ZB, ds, HP), f32),
            ((ZB,), jnp.int32)]
    text = _compile_text(
        lambda a, v, b, s0, keep: ssd_chain_commit_rows(
            {"a": a, "v": v, "b": b}, s0, keep, interpret=False), args,
        one_chip)
    _assert_one_named_kernel(text, r"^%ssd_chain_commit(\.\d+)? ")


def _shared_block_attention(prefill: bool):
    """zamba2-7b's shared-block attention over a paged pool, as the model
    calls it: verify (the paged tree kernel) or a prefill chunk."""
    from repro.configs import get_config
    from repro.models.attention import AttnInputs, gqa_fwd
    from repro.models.model import hybrid_scale
    cfg = get_config("zamba2-7b")

    def fn(x, wq, wk, wv, wo, pool_k, pool_v, cache_len, table, q_pos):
        ai = AttnInputs(q_pos=q_pos, cache_k=pool_k, cache_v=pool_v,
                        cache_len=cache_len, tree_mask=None, window=0,
                        causal=True, block_table=table, prefill=prefill)
        p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
        return gqa_fwd(p, cfg, x, ai, scale=hybrid_scale(cfg), kv_width=ZP)[0]

    T = 256 if prefill else ZT
    d2, N = 2 * cfg.d_model, 1 + ZB * ZM
    bf = jnp.bfloat16
    return fn, [((ZB if not prefill else 1, T, d2), bf), ((d2, ZH * ZD), bf),
                ((d2, ZH * ZD), bf), ((d2, ZH * ZD), bf),
                ((ZH * ZD, cfg.d_model), bf), ((N, ZH, 16, ZP), bf),
                ((N, ZH, 16, ZP), bf),
                ((ZB if not prefill else 1,), jnp.int32),
                ((ZB if not prefill else 1, ZM), jnp.int32),
                ((ZB if not prefill else 1, T), jnp.int32)]


def test_zamba2_paged_verify_compiles(one_chip, monkeypatch):
    # the model leaves ``interpret`` to the backend: steer it to the TPU's
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "resolve_backend", lambda: "tpu")
    fn, args = _shared_block_attention(prefill=False)
    _assert_one_named_kernel(_compile_text(fn, args, one_chip))


def test_zamba2_chunk_path_compiles(one_chip):
    fn, args = _shared_block_attention(prefill=True)
    text = _compile_text(fn, args, one_chip)
    assert "tpu_custom_call" not in text    # the chunk runs in XLA


def test_224_lane_page_walk_is_refused(one_chip):
    """Why the hybrid blocks' pool stores heads of 224 as 256 lanes: the
    page walk copies whole pages out of the pool in HBM, and Mosaic
    refuses a copy whose lane extent is not a whole number of 128-lane
    tiles."""
    b = ZB // 2              # a batch of its own: no cached trace is reused
    bf, N = jnp.bfloat16, 1 + ZB * ZM
    args = [((b, 8, ZH, ZD), bf), ((N, ZH, 16, ZD), bf),
            ((N, ZH, 16, ZD), bf), ((b, 8, ZH, ZD), bf),
            ((b, 8, ZH, ZD), bf), ((8, 8), jnp.bool_), ((b,), jnp.int32),
            ((b, ZM), jnp.int32)]
    with pytest.raises(Exception, match=r"aligned to tiling \(128\), but is 224"):
        _compile_text(
            lambda *a: tree_attention_paged_bshd(*a, interpret=False), args,
            one_chip)


@pytest.mark.parametrize("name", ["minitron-4b", "zamba2-7b"])
def test_draft_selection_has_no_sort(one_chip, name):
    """The served draft at the cells' shapes (16 rows; minitron-4b's
    1/4/6/4/1 tree over 256000 words, zamba2-7b's chain of 4 over 32000)
    picks each node's child without a sort: ``lax.top_k`` lowered to a
    full sort of every row, two thirds of minitron-4b's verify step."""
    from repro.configs import get_config
    from repro.core.heads import draft_tree_tokens, init_draft_params
    from repro.launch.specs import tree_for
    cfg = get_config(name)
    tree = tree_for(cfg)
    key = jax.random.PRNGKey(0)
    dp = jax.eval_shape(lambda: init_draft_params(key, cfg))
    d, V, dt = cfg.d_model, cfg.vocab_size, jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree.flatten(dp)

    def fn(embed, lm_head, h, last, *leaves):
        base = {"embed": embed, "lm_head": lm_head}
        return draft_tree_tokens(jax.tree.unflatten(treedef, leaves), cfg,
                                 base, tree, h, last)

    args = [((V, d), dt), ((d, V), dt), ((16, d), dt), ((16,), jnp.int32),
            *[(s.shape, s.dtype) for s in flat]]
    text = _compile_text(fn, args, one_chip)
    assert not re.search(r"\bsort\(", text)
