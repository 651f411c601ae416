"""Native paged tree-attention kernel tests (DESIGN.md §6.6).

Load-bearing invariants:

  * parity: streaming K/V blocks straight from the pool through the block
    table produces the same output as (a) the gather_view-style dense view
    fed to the dense kernel and (b) the pure-jnp paged oracle, across
    block sizes, ragged per-row ``cache_len``, and GQA grouping;
  * NULL-block isolation: table entries pointing at the reserved physical
    block 0 — unallocated tails AND holes punched below ``cache_len`` —
    are compute-skipped, so the NULL block's contents can NEVER reach the
    output;
  * the serving engine's native data path byte-matches the gather/scatter
    shim it replaced (the shim survives precisely as this oracle).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tree_attention.kernel import (tree_attention,
                                                 tree_attention_paged)
from repro.kernels.tree_attention.ops import tree_attention_paged_bshd
from repro.kernels.tree_attention.ref import (tree_attention_paged_ref,
                                              tree_attention_ref)
from repro.core.trees import default_tree


def _rand(key, i, shape):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def _cover_tables(lens, T, bs, M, num_blocks, holes=()):
    """Ascending-id tables covering [0, len + T) per row; optional
    ``holes``: (row, logical_block) entries punched back to NULL."""
    B = len(lens)
    table = np.zeros((B, M), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        need = -(-max(int(L) + T, 1) // bs)
        assert need <= M and nxt + need <= num_blocks
        for j in range(need):
            table[b, j] = nxt
            nxt += 1
    for b, j in holes:
        table[b, j] = 0
    return jnp.asarray(table)


def _gathered_view(pool, table):
    """The dense (B, Hkv, S, D) view of a head-major (N, Hkv, bs, D)
    pool that the old shim materialized."""
    B, M = table.shape
    _, Hkv, bs, D = pool.shape
    return pool[table].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * bs, D)


@pytest.mark.parametrize("bs,M,num_blocks", [(16, 8, 32), (128, 3, 8)])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2)])
def test_paged_parity_vs_dense_kernel_and_ref(rng, bs, M, num_blocks,
                                              Hq, Hkv):
    """Paged kernel == dense kernel on the gathered view == jnp oracle,
    for ragged per-row lens (including an empty row and a row whose last
    block is partially committed)."""
    B, T, D = 3, 8, 64
    lens = [bs * 2 + 5, 0, min(M * bs - T, bs * 3)]
    pool_k = _rand(rng, 0, (num_blocks, Hkv, bs, D))
    pool_v = _rand(rng, 1, (num_blocks, Hkv, bs, D))
    q = _rand(rng, 2, (B, Hq, T, D))
    tk = _rand(rng, 3, (B, Hkv, T, D))
    tv = _rand(rng, 4, (B, Hkv, T, D))
    tm = jnp.asarray(default_tree(T, 2, 3).ancestor_mask)
    lens_j = jnp.asarray(lens, jnp.int32)
    table = _cover_tables(lens, T, bs, M, num_blocks)

    o = tree_attention_paged(q, pool_k, pool_v, tk, tv, tm, lens_j, table,
                             interpret=True)
    ref = tree_attention_paged_ref(q, pool_k, pool_v, tk, tv, tm, lens_j,
                                   table)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    od = tree_attention(q, _gathered_view(pool_k, table),
                        _gathered_view(pool_v, table), tk, tv, tm, lens_j,
                        bk=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(od),
                               atol=2e-5, rtol=2e-5)


def test_paged_null_holes_are_masked(rng):
    """Tables may carry NULL holes BELOW cache_len (e.g. future prefix
    sharing / sparsity): the kernel must skip those blocks, matching the
    oracle which masks them — and must NOT match the dense view, which
    would read the NULL block's garbage at the hole."""
    B, Hq, Hkv, T, D, bs, M, N = 2, 2, 2, 8, 64, 16, 6, 16
    lens = [bs * 4, bs * 3 + 7]
    pool_k = _rand(rng, 10, (N, Hkv, bs, D))
    pool_v = _rand(rng, 11, (N, Hkv, bs, D))
    q = _rand(rng, 12, (B, Hq, T, D))
    tk = _rand(rng, 13, (B, Hkv, T, D))
    tv = _rand(rng, 14, (B, Hkv, T, D))
    tm = jnp.tril(jnp.ones((T, T), bool))
    lens_j = jnp.asarray(lens, jnp.int32)
    holes = [(0, 1), (1, 0)]                 # both strictly below cache_len
    table = _cover_tables(lens, T, bs, M, N, holes=holes)

    o = tree_attention_paged(q, pool_k, pool_v, tk, tv, tm, lens_j, table,
                             interpret=True)
    ref = tree_attention_paged_ref(q, pool_k, pool_v, tk, tv, tm, lens_j,
                                   table)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    unmasked = tree_attention_ref(q, _gathered_view(pool_k, table),
                                  _gathered_view(pool_v, table), tk, tv, tm,
                                  lens_j)
    assert float(jnp.max(jnp.abs(o - unmasked))) > 1e-3, \
        "holes were read, not skipped (matches the unmasked dense view)"


def test_null_block_contents_never_influence_output(rng):
    """Poisoning physical block 0 with huge garbage must not change a
    single output bit — neither via unallocated tail entries nor via
    holes below cache_len."""
    B, Hq, Hkv, T, D, bs, M, N = 2, 4, 2, 8, 64, 16, 6, 16
    lens = [bs * 2 + 3, bs * 3]
    pool_k = _rand(rng, 20, (N, Hkv, bs, D))
    pool_v = _rand(rng, 21, (N, Hkv, bs, D))
    q = _rand(rng, 22, (B, Hq, T, D))
    tk = _rand(rng, 23, (B, Hkv, T, D))
    tv = _rand(rng, 24, (B, Hkv, T, D))
    tm = jnp.asarray(default_tree(T, 2, 3).ancestor_mask)
    lens_j = jnp.asarray(lens, jnp.int32)
    table = _cover_tables(lens, T, bs, M, N, holes=[(1, 1)])

    outs = []
    for fill in (0.0, 1e4, -1e4):
        pk = pool_k.at[0].set(fill)
        pv = pool_v.at[0].set(fill)
        outs.append(np.asarray(tree_attention_paged(
            q, pk, pv, tk, tv, tm, lens_j, table, interpret=True)))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_paged_bshd_wrapper_pads_T(rng):
    """ops.py pads T to a sublane multiple around the paged kernel;
    the sliced-back result must match the unpadded oracle."""
    B, T, Hq, Hkv, D, bs, M, N = 2, 13, 2, 1, 64, 16, 6, 16
    tree = default_tree(13, 4, 4)
    tm = jnp.asarray(tree.ancestor_mask)
    lens = [9, bs * 2 + 1]
    pool_k = _rand(rng, 30, (N, Hkv, bs, D))
    pool_v = _rand(rng, 31, (N, Hkv, bs, D))
    q = _rand(rng, 32, (B, T, Hq, D))
    tk = _rand(rng, 33, (B, T, Hkv, D))
    tv = _rand(rng, 34, (B, T, Hkv, D))
    lens_j = jnp.asarray(lens, jnp.int32)
    table = _cover_tables(lens, T, bs, M, N)
    tr = lambda t: t.transpose(0, 2, 1, 3)

    o = tree_attention_paged_bshd(q, pool_k, pool_v, tk, tv, tm, lens_j,
                                  table)
    ref = tree_attention_paged_ref(tr(q), pool_k, pool_v, tr(tk), tr(tv),
                                   tm, lens_j, table)
    np.testing.assert_allclose(np.asarray(tr(o)), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# engine-level: the native data path vs the shim it replaced
# ---------------------------------------------------------------------------


def test_engine_native_matches_shim_oracle():
    """The gather/scatter shim survives as the parity oracle: serving the
    same ragged workload through ``paged_attention='native'`` and
    ``'shim'`` must produce byte-identical token streams."""
    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.models.model import init_params
    from repro.serving.engine import PagedSpeculativeEngine, Request

    rng = jax.random.PRNGKey(0)
    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    params = init_params(rng, cfg)
    dp = init_draft_params(jax.random.fold_in(rng, 1), cfg)
    tree = default_tree(8, 2, 3)
    rs = np.random.RandomState(5)
    prompts = [(rs.randint(0, cfg.vocab_size, n).astype(np.int32), b)
               for n, b in ((16, 10), (23, 8), (9, 12))]

    outs = {}
    for mode in ("native", "shim"):
        eng = PagedSpeculativeEngine(params, dp, cfg, tree, max_len=192,
                                     block_size=16, num_blocks=17,
                                     paged_attention=mode)
        reqs = [Request(prompt=p.copy(), max_new_tokens=b)
                for p, b in prompts]
        stats = eng.serve(reqs, max_batch=2)
        outs[mode] = [r.output for r in reqs]
        # native transient: scratch writes only; shim: the dense view
        expect = 2 * (tree.size if mode == "native"
                      else eng.blocks_per_slot * eng.block_size)
        assert stats.step_transient_tokens == expect
    assert outs["native"] == outs["shim"]


def test_engine_native_ar_step_matches_dense():
    """The non-speculative baseline (T=1 chain through the padded paged
    kernel) must also byte-match: paged native == paged shim == dense."""
    from repro.configs import get_config
    from repro.models.model import init_params
    from repro.serving.engine import (PagedSpeculativeEngine, Request,
                                      SpeculativeEngine)

    rng = jax.random.PRNGKey(2)
    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    params = init_params(rng, cfg)
    tree = default_tree(8, 2, 3)
    rs = np.random.RandomState(9)
    prompts = [(rs.randint(0, cfg.vocab_size, n).astype(np.int32), b)
               for n, b in ((16, 8), (21, 6), (11, 7))]

    def serve(make):
        eng = make()
        reqs = [Request(prompt=p.copy(), max_new_tokens=b)
                for p, b in prompts]
        eng.serve(reqs, max_batch=2)
        return [r.output for r in reqs]

    dense = serve(lambda: SpeculativeEngine(
        params, None, cfg, tree, max_len=192, use_speculative=False))
    for mode in ("native", "shim"):
        paged = serve(lambda: PagedSpeculativeEngine(
            params, None, cfg, tree, max_len=192, use_speculative=False,
            block_size=16, paged_attention=mode))
        assert paged == dense, mode
