"""Compile the paged attention-template kernels for a TPU v5e that is
described, not attached (``interpret=False``).

These are the verify kernels of ``PagedSpeculativeEngine`` at the widths
it serves: minitron-4b's GQA (Hq=24 over Hkv=8 heads of 128, bf16) plain
and sliding-window, and DeepSeek-V2-Lite's absorbed MLA (r=512, rd=64).
Mosaic refuses tiles and ops here that the interpreter accepts, so each
test asserts that the program holds exactly one ``tpu_custom_call`` — the
kernel was compiled, not interpreted, as one call — named after the jitted
``tree_attention_template`` as the chip benchmark's trace reader expects
(``bench/work/tree_attn.py::OP_PATTERN``).  One test checks a refusal
instead: the reason absorbed MLA does not walk its pages.

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


def _assert_one_named_kernel(text: str) -> None:
    """One Mosaic kernel, its HLO instruction named as the benchmark's
    trace reader finds it (``%tree_attention_template.N = ...``)."""
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert re.match(_op_pattern(), calls[0]), calls[0][:120]


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
