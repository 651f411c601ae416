"""The draft heads' child selection (``core/heads.py::top_k_at_ranks``) is
exactly ``lax.top_k`` read at each node's child rank: the same values, bit
for bit, and the same indices, ties ranked by lower index.  ``lax.top_k``
stays here as the reference; the served path never sorts (the compiled
check is in ``tests/test_tpu_compile.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import DraftConfig
from repro.core import heads
from repro.core.heads import (draft_tree_tokens, init_draft_params,
                              top_k_at_ranks)
from repro.core.trees import chain_tree, default_tree
from repro.models.model import init_params


def _top_k_reference(x, ranks):
    """``lax.top_k`` over the last axis, read at ``ranks`` (the selection
    ``draft_tree_tokens`` made before it stopped sorting)."""
    vals, idxs = jax.lax.top_k(x, int(np.max(ranks)) + 1)
    r = jnp.broadcast_to(jnp.asarray(ranks)[:, None], vals.shape[:-1] + (1,))
    return (jnp.take_along_axis(vals, r, axis=-1)[..., 0],
            jnp.take_along_axis(idxs, r, axis=-1)[..., 0])


def _bits(a):
    return np.asarray(a).view(np.int32)


def _planted_rows(V, n, kmax, seed):
    """(6, n, V) float32, one kind of row per batch entry: random values;
    ties at the top; ties that cross the k boundary; -inf entries (fewer
    finite entries than k); every value equal; signed zeros and NaNs."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((6, n, V)).astype(np.float32)
    pos = rng.choice(V, size=(n, 2 * kmax + 2), replace=False)
    for j in range(n):
        # 1: the top is one value at kmax + 1 positions, spread over the row
        x[1, j, pos[j, :kmax + 1]] = 9.0
        # 2: kmax - 1 distinct leaders, then a tie of three that straddles k
        x[2, j, pos[j, :kmax - 1]] = 20.0 + np.arange(kmax - 1)
        x[2, j, pos[j, kmax - 1:kmax + 2]] = 7.5
    x[3, :, :] = -np.inf                     # -inf everywhere but a few
    for j in range(n):
        x[3, j, pos[j, :max(kmax - 2, 0)]] = rng.standard_normal(
            max(kmax - 2, 0))
    x[3, :, 1::97] = -np.inf
    x[4, :, :] = -1.25                       # every value equal
    x[5, :, :4] = [-0.0, 0.0, np.nan, -np.nan]
    return x


@pytest.mark.parametrize("V", [256000, 32003])
@pytest.mark.parametrize("kmax", [1, 2, 3, 4])
def test_top_k_at_ranks_equals_top_k(V, kmax):
    ranks = np.array(list(range(kmax)) + [kmax - 1])      # every rank < k
    x = jnp.asarray(_planted_rows(V, len(ranks), kmax, seed=V + kmax))
    val, idx = jax.jit(lambda a: top_k_at_ranks(a, ranks))(x)
    ref_val, ref_idx = jax.jit(lambda a: _top_k_reference(a, ranks))(x)
    assert val.dtype == jnp.float32 and idx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(_bits(val), _bits(ref_val))
    # the all-equal row ranks by index
    np.testing.assert_array_equal(np.asarray(idx)[4], ranks)


def _tiny(kind):
    draft = DraftConfig(kind=kind, n_heads=4,
                        n_mlp_layers=4 if kind == "hydra++" else 1,
                        prefix_attention=kind == "hydra++")
    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32",
                              draft=draft)
    key = jax.random.PRNGKey(17)
    return (cfg, init_params(key, cfg),
            init_draft_params(jax.random.fold_in(key, 1), cfg))


@pytest.mark.parametrize("kind", ["hydra++", "medusa"])
@pytest.mark.parametrize("tree", [default_tree(16, 4, 4), chain_tree(4)],
                         ids=["default_tree16", "chain_tree4"])
@pytest.mark.parametrize("h_scale", [1.0, 0.0], ids=["h", "h0"])
def test_draft_tree_tokens_bit_identical_to_top_k(kind, tree, h_scale,
                                                  monkeypatch):
    """tokens and logp (``chain_rejection_verify``'s ``draft_logp``) equal
    the ``lax.top_k`` version's bit for bit.  h = 0 makes every Medusa
    logit equal, so the whole tree is chosen by tie-breaking."""
    cfg, params, dp = _tiny(kind)
    key = jax.random.PRNGKey(3)
    h = h_scale * jax.random.normal(key, (3, cfg.d_model), jnp.float32)
    last = jax.random.randint(jax.random.fold_in(key, 1), (3,), 0,
                              cfg.vocab_size)

    def draft():    # a new function each time: no trace is shared
        return jax.jit(lambda h, last: draft_tree_tokens(
            dp, cfg, params, tree, h, last))

    tok, logp = draft()(h, last)
    monkeypatch.setattr(heads, "top_k_at_ranks", _top_k_reference)
    ref_tok, ref_logp = draft()(h, last)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))
    np.testing.assert_array_equal(_bits(logp), _bits(ref_logp))
