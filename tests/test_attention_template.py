"""Attention-template invariants (DESIGN.md §11):

  * bit-identity: at their pre-refactor default block sizes, all four
    legacy entry points produce BYTE-identical outputs to the frozen
    pre-refactor kernels in ``tests/_legacy_kernels.py``;
  * oracle parity: the template-only instantiations (windowed paged
    verify, absorbed-MLA paged verify) match independent pure-jnp
    oracles across block sizes, ragged cache lengths and windows;
  * NULL-block hygiene: reserved/hole pool blocks never influence any
    instantiation's output, whatever garbage they hold;
  * block legalization: requested sizes that don't tile the sequence
    are pad-or-clamped (never an assert), ValueError only when truly
    impossible;
  * autotuner: winners from the committed cache are valid block sizes
    (same math at a non-default point);
  * engine: gemma3-style sliding-window and deepseek-style MLA configs
    serve byte-identical token streams through native paged kernels vs
    the gather-shim oracle, with the native transient footprint.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _legacy_kernels import (legacy_flash_attention, legacy_tree_attention,
                             legacy_tree_attention_paged)
from repro.kernels import autotune_cache_path, block_size_key
from repro.kernels.attention_template import (mla_attention_paged_bshd,
                                              self_attention,
                                              tree_attention_paged_windowed_bshd)
from repro.kernels.attention_template.ref import (
    mla_attention_paged_ref, tree_attention_paged_windowed_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.tree_attention.kernel import (tree_attention,
                                                 tree_attention_paged)
from repro.kernels.tree_attention.ops import tree_attention_paged_bshd
from repro.kernels.tree_attention.ref import tree_attention_ref
from repro.core.trees import default_tree

_B, _HQ, _HKV, _T, _D = 2, 4, 2, 13, 64


def _rand(key, i, shape):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def _cover_tables(lens, T, bs, M, num_blocks, holes=()):
    """Per-slot block tables covering lens[b]+T tokens; optional holes
    are NULL entries inside the covered range."""
    table = np.zeros((_B, M), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for j in range(-(-(int(L) + T) // bs)):
            table[b, j] = nxt
            nxt += 1
    for b, j in holes:
        table[b, j] = 0
    assert nxt <= num_blocks
    return jnp.asarray(table)


def _tree_inputs(rng, S, lens):
    q = _rand(rng, 0, (_B, _HQ, _T, _D))
    tk = _rand(rng, 3, (_B, _HKV, _T, _D))
    tv = _rand(rng, 4, (_B, _HKV, _T, _D))
    tm = np.asarray(default_tree(_T, 2, 3).ancestor_mask)
    return q, tk, tv, jnp.asarray(tm), jnp.asarray(lens, jnp.int32)


# ---------------------------------------------------------------------------
# bit-identity vs the frozen pre-refactor kernels (default block sizes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_bit_identity_vs_legacy(rng, causal, window):
    S = 256
    q = _rand(rng, 0, (_B, _HQ, S, _D))
    k = _rand(rng, 1, (_B, _HKV, S, _D))
    v = _rand(rng, 2, (_B, _HKV, S, _D))
    new = flash_attention(q, k, v, causal=causal, window=window,
                          bq=128, bk=128)
    old = legacy_flash_attention(q, k, v, causal=causal, window=window,
                                 bq=128, bk=128)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_tree_dense_bit_identity_vs_legacy(rng):
    S = 256
    lens = [100, 243]
    q, tk, tv, tm, lens = _tree_inputs(rng, S, lens)
    ck = _rand(rng, 1, (_B, _HKV, S, _D))
    cv = _rand(rng, 2, (_B, _HKV, S, _D))
    new = tree_attention(q, ck, cv, tk, tv, tm, lens, bk=512)
    old = legacy_tree_attention(q, ck, cv, tk, tv, tm, lens, bk=512)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("bs", [16, 128])
def test_tree_paged_bit_identity_vs_legacy(rng, bs):
    lens = [37, 120]
    M = -(-(max(lens) + _T) // bs) + 1
    N = 2 * M + 2
    q, tk, tv, tm, lens = _tree_inputs(rng, 0, lens)
    pk = _rand(rng, 1, (N, _HKV, bs, _D))
    pv = _rand(rng, 2, (N, _HKV, bs, _D))
    table = _cover_tables([int(x) for x in lens], _T, bs, M, N)
    new = tree_attention_paged(q, pk, pv, tk, tv, tm, lens, table)
    # the frozen kernel reads the old token-major (N, bs, Hkv, D) pool
    tok_major = lambda p: p.transpose(0, 2, 1, 3)
    old = legacy_tree_attention_paged(q, tok_major(pk), tok_major(pv), tk,
                                      tv, tm, lens, table)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# ---------------------------------------------------------------------------
# new instantiations vs independent oracles
# ---------------------------------------------------------------------------


def _windowed_case(rng, bs, holes=()):
    lens = [37, 120]
    M = -(-(max(lens) + _T) // bs) + 1
    N = 2 * M + 2
    pk = _rand(rng, 1, (N, _HKV, bs, _D))
    pv = _rand(rng, 2, (N, _HKV, bs, _D))
    q, tk, tv, tm, lens_j = _tree_inputs(rng, 0, lens)
    table = _cover_tables(lens, _T, bs, M, N, holes=holes)
    depth = jnp.asarray(default_tree(_T, 2, 3).depth, jnp.int32)
    q_pos = lens_j[:, None] + depth[None, :]
    return q, pk, pv, tk, tv, tm, lens_j, table, q_pos


@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("window", [0, 24, 64])
def test_windowed_paged_matches_ref(rng, bs, window):
    q, pk, pv, tk, tv, tm, lens, table, q_pos = _windowed_case(rng, bs)
    w = jnp.int32(window)
    out = tree_attention_paged_windowed_bshd(
        q.transpose(0, 2, 1, 3), pk, pv, tk.transpose(0, 2, 1, 3),
        tv.transpose(0, 2, 1, 3), tm, lens, table, q_pos, w)
    ref = tree_attention_paged_windowed_ref(q, pk, pv, tk, tv, tm, lens,
                                            table, q_pos, w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-5)


def test_windowed_w0_is_bitwise_plain_paged(rng):
    """A traced window <= 0 must be an exact mask no-op: one compiled
    kernel serves scan groups mixing local and global layers."""
    bs = 16
    q, pk, pv, tk, tv, tm, lens, table, q_pos = _windowed_case(rng, bs)
    win = tree_attention_paged_windowed_bshd(
        q.transpose(0, 2, 1, 3), pk, pv, tk.transpose(0, 2, 1, 3),
        tv.transpose(0, 2, 1, 3), tm, lens, table, q_pos, jnp.int32(0),
        pad_to=8)
    plain = tree_attention_paged_bshd(
        q.transpose(0, 2, 1, 3), pk, pv, tk.transpose(0, 2, 1, 3),
        tv.transpose(0, 2, 1, 3), tm, lens, table, pad_to=8)
    np.testing.assert_array_equal(np.asarray(win), np.asarray(plain))


def _mla_case(rng, bs, r=64, rd=16, holes=()):
    lens = [37, 120]
    M = -(-(max(lens) + _T) // bs) + 1
    N = 2 * M + 2
    ql = _rand(rng, 0, (_B, _T, _HQ, r))
    qr = _rand(rng, 1, (_B, _T, _HQ, rd))
    pl_ = _rand(rng, 2, (N, bs, r))
    pr_ = _rand(rng, 3, (N, bs, rd))
    tl = _rand(rng, 4, (_B, _T, r))
    trp = _rand(rng, 5, (_B, _T, rd))
    tm = jnp.asarray(np.asarray(default_tree(_T, 2, 3).ancestor_mask))
    lens_j = jnp.asarray(lens, jnp.int32)
    table = _cover_tables(lens, _T, bs, M, N, holes=holes)
    scale = 1.0 / float(np.sqrt(r // 2 + rd))
    return ql, qr, pl_, pr_, tl, trp, tm, lens_j, table, scale


@pytest.mark.parametrize("bs", [16, 128])
def test_mla_paged_matches_ref(rng, bs):
    ql, qr, pl_, pr_, tl, trp, tm, lens, table, scale = _mla_case(rng, bs)
    out = mla_attention_paged_bshd(ql, qr, pl_, pr_, tl, trp, tm, lens,
                                   table, scale=scale)
    ref = mla_attention_paged_ref(ql, qr, pl_, pr_, tl, trp, tm, lens,
                                  table, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("fill", [0.0, 1e4, -1e4])
def test_null_blocks_never_influence_windowed_or_mla(rng, fill):
    """Poison the reserved block AND a mid-table hole: the output must be
    byte-identical for every fill value (compute-skip, not just mask)."""
    holes = [(1, 1)]
    outs = []
    for f in (0.0, fill):
        q, pk, pv, tk, tv, tm, lens, table, q_pos = _windowed_case(
            rng, 16, holes=holes)
        null_rows = jnp.arange(pk.shape[0]) == 0
        pk = jnp.where(null_rows[:, None, None, None], f, pk)
        pv = jnp.where(null_rows[:, None, None, None], f, pv)
        outs.append(tree_attention_paged_windowed_bshd(
            q.transpose(0, 2, 1, 3), pk, pv, tk.transpose(0, 2, 1, 3),
            tv.transpose(0, 2, 1, 3), tm, lens, table, q_pos,
            jnp.int32(64)))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))

    outs = []
    for f in (0.0, fill):
        ql, qr, pl_, pr_, tl, trp, tm, lens, table, scale = _mla_case(
            rng, 16, holes=holes)
        null_rows = jnp.arange(pl_.shape[0]) == 0
        pl_ = jnp.where(null_rows[:, None, None], f, pl_)
        pr_ = jnp.where(null_rows[:, None, None], f, pr_)
        outs.append(mla_attention_paged_bshd(ql, qr, pl_, pr_, tl, trp,
                                             tm, lens, table, scale=scale))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


# ---------------------------------------------------------------------------
# block sizes: autotuned winners + legalization
# ---------------------------------------------------------------------------


def test_flash_multiple_block_points_including_autotuned(rng):
    """Same math at several (bq, bk) tilings, one of which is the
    committed autotuner winner (a non-default point on CPU)."""
    S = 256
    q = _rand(rng, 0, (_B, _HQ, S, _D))
    k = _rand(rng, 1, (_B, _HKV, S, _D))
    v = _rand(rng, 2, (_B, _HKV, S, _D))
    base = flash_attention(q, k, v, window=64, bq=128, bk=128)
    with open(autotune_cache_path("cpu")) as f:
        entry = json.load(f)["entries"][block_size_key("flash", _D)]
    winner = (int(entry["bq"]), int(entry["bk"]))
    points = {(64, 64), (256, 256), winner}
    assert len(points) >= 2
    for bq, bk in points:
        out = flash_attention(q, k, v, window=64, bq=bq, bk=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-5, atol=2e-5)


def test_self_attention_legalizes_odd_lengths(rng):
    """S=52 with bq=bk=8 has no >=8 divisor clamp: the template must pad
    to 56 and mask the tail, not assert."""
    for S, bq, bk in ((52, 8, 8), (100, 128, 64), (96, 128, 128)):
        q = _rand(rng, 0, (_B, _HQ, S, _D))
        k = _rand(rng, 1, (_B, _HKV, S, _D))
        v = _rand(rng, 2, (_B, _HKV, S, _D))
        out = self_attention(q, k, v, window=24, bq=bq, bk=bk)
        ref = flash_attention_ref(q, k, v, window=24)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5), (S, bq, bk)


def test_tree_dense_legalizes_odd_cache(rng):
    """S=52 with bk=8 pads the cache tail; the pad is masked by
    cache_len so the oracle must still match."""
    S = 52
    lens = [20, 52]
    q, tk, tv, tm, lens = _tree_inputs(rng, S, lens)
    ck = _rand(rng, 1, (_B, _HKV, S, _D))
    cv = _rand(rng, 2, (_B, _HKV, S, _D))
    out = tree_attention(q, ck, cv, tk, tv, tm, lens, bk=8)
    ref = tree_attention_ref(q, ck, cv, tk, tv, tm, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_impossible_blocks_raise_value_error(rng):
    q = _rand(rng, 0, (_B, _HQ, 64, _D))
    k = _rand(rng, 1, (_B, _HKV, 64, _D))
    with pytest.raises(ValueError):
        self_attention(q, k, k, bq=0, bk=128)
    with pytest.raises(ValueError):
        self_attention(q, k, k, bq=128, bk=-8)

    bs = 12   # pool block size not a multiple of 8: truly impossible
    q, pk, pv, tk, tv, tm, lens, table, q_pos = _windowed_case(rng, bs)
    with pytest.raises(ValueError):
        tree_attention_paged(q, pk, pv, tk, tv, tm, lens, table)


# ---------------------------------------------------------------------------
# engine-level byte parity: every group native, gather shim as oracle
# ---------------------------------------------------------------------------


def _serve_both_modes(cfg_name, seed):
    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.models.model import init_params
    from repro.serving.engine import PagedSpeculativeEngine, Request

    rng = jax.random.PRNGKey(seed)
    cfg = dataclasses.replace(get_config(cfg_name).reduced(),
                              dtype="float32")
    params = init_params(rng, cfg)
    dp = init_draft_params(jax.random.fold_in(rng, 1), cfg)
    tree = default_tree(8, 2, 3)
    rs = np.random.RandomState(seed)
    prompts = [(rs.randint(0, cfg.vocab_size, n).astype(np.int32), b)
               for n, b in ((16, 10), (23, 8), (9, 12))]

    outs, transients = {}, {}
    for mode in ("native", "shim"):
        eng = PagedSpeculativeEngine(params, dp, cfg, tree, max_len=192,
                                     block_size=16, num_blocks=17,
                                     paged_attention=mode)
        reqs = [Request(prompt=p.copy(), max_new_tokens=b)
                for p, b in prompts]
        stats = eng.serve(reqs, max_batch=2)
        outs[mode] = [r.output for r in reqs]
        transients[mode] = stats.step_transient_tokens
        if mode == "native":
            assert stats.step_transient_tokens == 2 * tree.size
        else:
            assert stats.step_transient_tokens == (
                2 * eng.blocks_per_slot * eng.block_size)
    assert transients["native"] < transients["shim"]
    return outs


def test_engine_windowed_native_matches_shim_oracle():
    """gemma3-style sliding-window group: native windowed paged kernel vs
    the gather-shim oracle must be token-stream byte-identical."""
    outs = _serve_both_modes("gemma3-1b", 5)
    assert outs["native"] == outs["shim"]


def test_engine_mla_native_matches_shim_oracle():
    """deepseek-style MLA: absorbed-latent native paged kernel vs the
    gather-shim oracle must be token-stream byte-identical."""
    outs = _serve_both_modes("deepseek-v2-lite-16b", 7)
    assert outs["native"] == outs["shim"]
