"""Compile the paged attention-template kernels for a TPU v5e that is
described, not attached (``interpret=False``).

These are the verify kernels of ``PagedSpeculativeEngine`` at the widths
it serves: minitron-4b's GQA (Hq=24 over Hkv=8 heads of 128, bf16) plain
and sliding-window, and DeepSeek-V2-Lite's absorbed MLA (r=512, rd=64).
Mosaic refuses tiles and ops here that the interpreter accepts, so each
test asserts that the program holds a ``tpu_custom_call`` — the kernel
was compiled, not interpreted.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles, as
an entry for a described chip cannot be read back without one.
"""
import os

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
    assert "tpu_custom_call" in text


def test_gqa_paged_windowed_compiles(one_chip):
    bs = 16
    bf = jnp.bfloat16
    args = [((B, T, HQ, D), bf), ((N, HKV, bs, D), bf), ((N, HKV, bs, D), bf),
            ((B, T, HKV, D), bf), ((B, T, HKV, D), bf), ((T, T), jnp.bool_),
            *TABLE, ((B, T), jnp.int32), ((), jnp.int32)]
    text = _compile_text(
        lambda *a: tree_attention_paged_windowed_bshd(*a, interpret=False),
        args, one_chip)
    assert "tpu_custom_call" in text


def test_mla_paged_compiles(one_chip):
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
    assert "tpu_custom_call" in text
