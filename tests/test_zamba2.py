"""The hybrid program (Zamba2) against the plain float32 reference of the
benchmark's ``zamba2`` kind (``bench/kinds/zamba2.py``), which imports
nothing of the program, on seeded random weights at a ``reduced()``
size.  The reduced zamba2-7b keeps what the published one has: two
shared blocks invoked twice each, two B/C groups, a head width off the
128 lanes (96, stored as 128) and a LoRA adapter per invocation.

Tolerances, each the widest gap of float32 logits over the largest
logit: 2e-5 where program and reference compute the same float32
mathematics in another order (the chunked scan against the sequential
one, blocked against plain softmax, padded against unpadded heads) under
``highest`` matmul precision; seen: 5e-6 to 7e-6.  A recurrent state
rounded to bfloat16 reads 9.6e-5 and misses it
(``test_bf16_state_fails_the_tolerance``).
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import random_weights
from repro.launch.specs import tree_for
from repro.models.model import (forward, group_program, hybrid_kv_width,
                                init_cache)
from repro.serving.engine import PagedSpeculativeEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
CONFIGS = ["zamba2-7b", "zamba2-1.2b"]


@pytest.fixture(scope="module")
def kind():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        spec = importlib.util.spec_from_file_location(
            "kind_zamba2", os.path.join(ROOT, "bench", "kinds", "zamba2.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(os.path.join(ROOT, "bench"))
    return mod


def model_dict(cfg) -> dict:
    """The ``model`` of a configuration file, for ``cfg``."""
    s = cfg.ssm
    return dict(
        kind="zamba2", n_layers=cfg.n_layers, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
        vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_eps=cfg.rms_eps, tie_embeddings=cfg.tie_embeddings,
        hybrid_layer_ids=list(cfg.hybrid_layer_ids),
        num_mem_blocks=cfg.num_mem_blocks, adapter_rank=cfg.adapter_rank,
        attn_scale=(cfg.resolved_head_dim / 2) ** -0.5, mlp_act="gelu",
        mamba_expand=s.expand, mamba_headdim=s.head_dim,
        mamba_d_state=s.d_state, mamba_ngroups=s.n_groups,
        mamba_d_conv=s.conv_width, chunk_size=s.chunk_size)


def small(name):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


@pytest.fixture(scope="module")
def z7():
    cfg = small("zamba2-7b")
    params, dp = random_weights(cfg, 5)
    return cfg, params, dp


def gap(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / np.abs(b).max())


# -- structure ---------------------------------------------------------------

@pytest.mark.parametrize("name,want", [
    ("zamba2-7b", [("mamba_stack", 6), ("hybrid", 1), ("mamba_stack", 4),
                   ("hybrid", 1), ("mamba_stack", 5), ("hybrid", 1),
                   ("mamba_stack", 5), ("hybrid", 1)]),
    ("zamba2-1.2b", [("hybrid", 1), ("mamba_stack", 5)] * 6
     + [("hybrid", 1), ("mamba_stack", 1)]),
])
def test_group_program_follows_hybrid_layer_ids(name, want):
    cfg = get_config(name)
    prog = group_program(cfg)
    assert prog == want
    assert sum(n for _, n in prog) == cfg.n_layers
    # the hybrid groups sit at the published layer ids, in order
    at, starts = 0, []
    for kind, n in prog:
        if kind == "hybrid":
            starts.append(at)
        at += n
    assert starts == list(cfg.hybrid_layer_ids)


def test_reduced_keeps_the_published_structure():
    cfg = small("zamba2-7b")
    assert cfg.num_mem_blocks == 2 and len(cfg.hybrid_layer_ids) >= 4
    assert cfg.ssm.n_groups == 2 and cfg.resolved_head_dim % 128
    assert hybrid_kv_width(cfg) == 128
    assert hybrid_kv_width(get_config("zamba2-7b")) == 256


# -- the full forward ----------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_forward_matches_reference(name, kind):
    cfg = small(name)
    params, _ = random_weights(cfg, 3)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                         cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        out = forward(params, cfg, jnp.asarray(toks)[None],
                      jnp.arange(40)[None], mode="full")
    ref = kind.logits_at(params, model_dict(cfg), toks, np.arange(40))
    assert gap(out.logits[0], ref) < TOL


def _prefill_then_verify(cfg, params, toks, P):
    """Prefill ``toks[:P]`` into a cache, then the rest as one chain
    verify; returns the verify's logits (one row)."""
    B, L = 1, len(toks)
    with jax.default_matmul_precision("highest"):
        pre = forward(params, cfg, jnp.asarray(toks[:P])[None],
                      jnp.arange(P)[None], mode="full",
                      cache=init_cache(cfg, B, 64))
        cl = jnp.full((B,), P, jnp.int32)
        ver = forward(params, cfg, jnp.asarray(toks[P:])[None],
                      (P + jnp.arange(L - P))[None], mode="verify",
                      cache=pre.cache, cache_len=cl)
    return ver.logits[0]


def test_prefill_then_chain_verify_matches_reference(z7, kind):
    cfg, params, _ = z7
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (37,), 0,
                                         cfg.vocab_size))
    got = _prefill_then_verify(cfg, params, toks, 32)
    ref = kind.logits_at(params, model_dict(cfg), toks, np.arange(32, 37))
    assert gap(got, ref) < TOL


def test_bf16_state_fails_the_tolerance(z7, kind, monkeypatch):
    """The same comparison with the recurrent state rounded to bfloat16
    wherever the program keeps it (the prefill's final state and the
    state the chain starts from) misses the tolerance."""
    import repro.kernels.ssd_chain.ops as ops
    import repro.models.ssm as ssm
    bf = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)
    chunked, chain = ssm.mamba2_ssd_chunked, ops.ssd_chain_verify_bthd

    def chunked_bf16(*a, **kw):
        o, s = chunked(*a, **kw)
        return o, bf(s)

    def chain_bf16(C, Bm, x, dt, A, s0, **kw):
        return chain(C, Bm, x, dt, A, bf(s0), **kw)

    monkeypatch.setattr(ssm, "mamba2_ssd_chunked", chunked_bf16)
    monkeypatch.setattr(ops, "ssd_chain_verify_bthd", chain_bf16)
    cfg, params, _ = z7
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (37,), 0,
                                         cfg.vocab_size))
    got = _prefill_then_verify(cfg, params, toks, 32)
    ref = kind.logits_at(params, model_dict(cfg), toks, np.arange(32, 37))
    assert gap(got, ref) > TOL


# -- through the paged engine ---------------------------------------------------

def test_paged_engine_serves_the_reference(z7, kind):
    """Paged chunked prefill, then chain verify steps, through
    ``PagedSpeculativeEngine.serve``: every served token is the
    reference's best at its position, up to a gap of 2e-5 of the largest
    logit (float32 rounding where two logits nearly tie)."""
    cfg, params, dp = z7
    eng = PagedSpeculativeEngine(params, dp, cfg, tree_for(cfg), max_len=128,
                                 block_size=16, num_blocks=40,
                                 prefill_chunk=16)
    rs = np.random.RandomState(0)
    reqs = [Request(prompt=rs.randint(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=b) for n, b in ((20, 9), (37, 7), (5, 6))]
    eng.serve(reqs, max_batch=2)
    m = model_dict(cfg)
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        seq = np.concatenate([r.prompt, r.output])[:-1]
        pos = np.arange(len(r.prompt) - 1, len(seq))
        ref = np.asarray(kind.logits_at(params, m, seq, pos))
        worst = (ref.max(-1) - ref[np.arange(len(pos)), r.output]).max()
        assert worst <= TOL * np.abs(ref).max(), (r.output, worst)


# -- the chain kernel -------------------------------------------------------------

@pytest.mark.parametrize("B,T,G,ds,H,hd", [
    (2, 5, 2, 16, 8, 64),      # the reduced zamba2-7b layer
    (3, 4, 1, 8, 4, 64),       # one group
    (1, 5, 2, 64, 8, 32),      # half-lane heads: a block holds four
])
def test_chain_kernels_match_ref(B, T, G, ds, H, hd):
    """Interpret mode against ``ref.py``: the verify's outputs, and the
    commit's state after each row's first ``keep`` tokens against the
    reference's state after that token (0: the state as it was), to
    float32 rounding (1e-5 of the largest value; the reference sums C.S
    in another order)."""
    from repro.kernels.ssd_chain.ops import (ssd_chain_commit_rows,
                                             ssd_chain_verify_bthd)
    from repro.kernels.ssd_chain.ref import ssd_chain_ref
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + H), 6)
    C = jax.random.normal(ks[0], (B, T, G, ds), jnp.bfloat16)
    Bm = jax.random.normal(ks[1], (B, T, G, ds), jnp.bfloat16)
    x = jax.random.normal(ks[2], (B, T, H, hd), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[4], (H,)))
    s0 = jax.random.normal(ks[5], (B, ds, H * hd))
    y, chain = ssd_chain_verify_bthd(C, Bm, x, dt, A, s0, interpret=True)
    yr, sr = ssd_chain_ref(C, Bm, x, dt, A, s0)
    assert y.shape == (B, T, H, hd)
    assert gap(y, yr) < 1e-5
    keep = jnp.arange(B, dtype=jnp.int32) % (T + 1)
    got = ssd_chain_commit_rows(chain, s0, keep, interpret=True)
    for b in range(B):
        k = int(keep[b])
        want = s0[b] if k == 0 else sr[b, k - 1]
        assert gap(got[b], want) < 1e-5, (b, k)
    np.testing.assert_array_equal(np.asarray(got[keep == 0]),
                                  np.asarray(s0[keep == 0]))
    # a layer stack before the rows commits each layer from its own state
    two = jax.tree.map(lambda t: jnp.stack([t, t]), chain)
    both = ssd_chain_commit_rows(two, jnp.stack([s0, 2 * s0]), keep,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


def test_grouped_heads_and_norm_match_reference(kind):
    """One Mamba2 layer with two B/C groups, full and verify, against the
    reference layer: head i reads group i // (H / G), and the gated
    RMSNorm is taken over each group's slice.  Swapping the groups' B
    and C columns moves the output, so the comparison can see them."""
    from repro.models.ssm import init_mamba2, mamba2_fwd
    cfg = small("zamba2-7b")
    p = init_mamba2(jax.random.PRNGKey(7), cfg, jnp.float32)
    p = dict(p, norm=0.3 * jax.random.normal(jax.random.PRNGKey(8),
                                             p["norm"].shape))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 24, cfg.d_model))
    m = model_dict(cfg)
    with jax.default_matmul_precision("highest"):
        ref = kind._mamba(p, m, x[0], None)
        full, st = mamba2_fwd(p, cfg, x[:, :20], mode="full")
        ver, _ = mamba2_fwd(p, cfg, x[:, 20:], mode="verify",
                            ssd_state=st["ssd_state"],
                            conv_state=st["conv_win"])
        got = jnp.concatenate([full, ver], axis=1)[0]
        assert gap(got, ref) < TOL
        d_in, G, N = 2 * cfg.d_model, 2, cfg.ssm.d_state
        w = p["w_in"]
        b0, b1 = d_in + d_in, d_in + d_in + N        # B of group 0 and 1
        c0, c1 = b0 + G * N, b1 + G * N
        perm = np.arange(w.shape[1])
        for a, b in ((b0, b1), (c0, c1)):
            perm[a:a + N], perm[b:b + N] = (np.arange(b, b + N),
                                            np.arange(a, a + N))
        swapped = kind._mamba(dict(p, w_in=w[:, perm]), m, x[0], None)
        assert gap(swapped, ref) > 100 * TOL


def test_invocations_have_their_own_adapters_and_caches(z7):
    """Four invocations of two blocks: each hybrid layer has its own
    LoRA adapter and output projection, and its own KV cache (the same
    block's two invocations write different keys)."""
    cfg, params, _ = z7
    hyb = [i for i, (k, _) in enumerate(group_program(cfg)) if k == "hybrid"]
    assert len(hyb) == 4 and len(params["shared"]) == 2
    lora = [np.asarray(params["groups"][i]["lora_a"]) for i in hyb]
    assert all(not np.array_equal(lora[0], a) for a in lora[1:])
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 0,
                              cfg.vocab_size)
    pos = jnp.arange(12)[None]
    base = forward(params, cfg, toks, pos, mode="full",
                   cache=init_cache(cfg, 1, 16))
    keys = [np.asarray(base.cache[i]["k"]) for i in hyb]
    assert keys[0].shape[-1] == hybrid_kv_width(cfg)
    # block 0 runs invocations 0 and 2: same weights, different caches
    assert not np.allclose(keys[0], keys[2])
    # the padded lanes stay zero
    assert not np.any(keys[0][..., cfg.resolved_head_dim:])
    # moving invocation 2's adapter moves the logits; its block's other
    # invocation keeps its own
    groups = list(params["groups"])
    groups[hyb[2]] = dict(groups[hyb[2]],
                          lora_b=groups[hyb[2]]["lora_b"] * 2.0)
    moved = forward(dict(params, groups=groups), cfg, toks, pos,
                    mode="full", cache=init_cache(cfg, 1, 16))
    assert gap(moved.logits, base.logits) > 1e-3
    np.testing.assert_array_equal(np.asarray(moved.cache[hyb[0]]["k"]),
                                  keys[0])
    assert not np.allclose(np.asarray(moved.cache[hyb[3]]["k"]), keys[3])
