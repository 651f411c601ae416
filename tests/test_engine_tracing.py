"""The serving program's profiler instrumentation (DESIGN.md §7).

  * the serve loop writes ``engine.*`` host spans into the profiler's
    trace, nested under one ``engine.iteration`` per loop pass, and the
    spans of one request carry its ``rid``;
  * every jitted program has a name (``jit_verify_step``, ``jit_join``,
    ``jit_prefill_chunk``, ``jit_prefill_chunk_final``), and the verify
    step's phases are named scopes in its op metadata;
  * ``EngineStats.kv_tokens_attended`` sums the cached context of every
    live row of every harvested step.
"""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.heads import init_draft_params
from repro.core.trees import default_tree
from repro.models.model import init_params
from repro.serving.engine import (PagedSpeculativeEngine, Request,
                                  SpeculativeEngine)

MAX_LEN = 128


@pytest.fixture(scope="module")
def setup():
    rng = jax.random.PRNGKey(0)
    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    params = init_params(rng, cfg)
    dp = init_draft_params(jax.random.fold_in(rng, 1), cfg)
    return cfg, params, dp, default_tree(8, 2, 3)


def _requests(cfg, shapes, seed=0):
    rs = np.random.RandomState(seed)
    return [Request(prompt=rs.randint(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=b) for n, b in shapes]


def _engine_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every ``engine.*`` host span."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if not p.name.startswith("/device:")
            for line in p.lines for e in line.events
            if e.name.startswith("engine.")]


def test_paged_serve_writes_engine_spans(setup, tmp_path):
    cfg, params, dp, tree = setup
    eng = PagedSpeculativeEngine(params, dp, cfg, tree, max_len=MAX_LEN,
                                 block_size=16, prefill_chunk=16)
    eng.serve(_requests(cfg, [(20, 3), (9, 3)]), max_batch=2)   # compiles
    reqs = _requests(cfg, [(40, 4), (12, 5)], seed=1)
    steps = eng.stats.steps
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(reqs, max_batch=2, warmup=False)
    ev = _engine_spans(tmp_path)
    by = lambda n: [e for e in ev if e[0] == n]
    iters = by("engine.iteration")
    assert [e[3]["step_num"] for e in iters] == list(range(len(iters)))
    for name in ("engine.poll", "engine.admit", "engine.alloc",
                 "engine.dispatch", "engine.read", "engine.harvest"):
        spans = by(name)
        assert spans, name
        # every child lies inside one iteration
        assert all(any(i[1] <= s and e <= i[2] for i in iters)
                   for _, s, e, _ in spans), name
    assert len(by("engine.dispatch")) == eng.stats.steps - steps
    chunks = by("engine.prefill_chunk")
    assert {c[3]["rid"] for c in chunks} == {r.rid for r in reqs}
    # the 40-token prompt streams in three 16-token chunks, the last final
    long = sorted((c for c in chunks if c[3]["rid"] == reqs[0].rid),
                  key=lambda c: c[1])
    assert [(c[3]["start"], bool(c[3]["final"])) for c in long] == [
        (0, False), (16, False), (32, True)]


def test_requests_get_distinct_rids(setup):
    cfg, params, dp, tree = setup
    eng = SpeculativeEngine(params, dp, cfg, tree, max_len=MAX_LEN)
    a, b = _requests(cfg, [(8, 2), (8, 2)])
    eng.submit(a)
    eng.serve([b], max_batch=2)
    assert {a.rid, b.rid} == {0, 1}


def test_verify_step_is_named_with_phase_scopes(setup):
    cfg, params, _, tree = setup
    cfg2 = dataclasses.replace(
        cfg, draft=dataclasses.replace(cfg.draft, prefix_attention=True,
                                       n_mlp_layers=2))
    dp2 = init_draft_params(jax.random.PRNGKey(11), cfg2)
    eng = PagedSpeculativeEngine(params, dp2, cfg2, tree, max_len=MAX_LEN,
                                 block_size=16)
    text = eng.lower_step(2).as_text(dialect="hlo", debug_info=True)
    assert text.startswith("HloModule jit_verify_step")
    scopes = set(re.findall(r'op_name="jit\(verify_step\)/(\w+)/', text))
    assert {"draft", "verify", "accept", "commit", "draft_prefix"} <= scopes


@pytest.mark.parametrize("final", [False, True])
def test_prefill_programs_are_named(setup, final):
    cfg, params, dp, tree = setup
    eng = PagedSpeculativeEngine(params, dp, cfg, tree, max_len=MAX_LEN,
                                 block_size=16, prefill_chunk=16)
    state = eng._init_pool(2, jax.random.PRNGKey(0))
    text = eng._chunk_fns[final].lower(
        params, dp, state, jnp.zeros(16, jnp.int32), jnp.int32(0),
        jnp.int32(1), jnp.int32(0), jnp.zeros(eng.blocks_per_slot, jnp.int32),
        eng._view_blocks(64)).as_text(dialect="hlo")
    name = "prefill_chunk_final" if final else "prefill_chunk"
    assert text.startswith(f"HloModule jit_{name},")


def test_join_program_is_named(setup):
    cfg, params, dp, tree = setup
    eng = SpeculativeEngine(params, dp, cfg, tree, max_len=MAX_LEN)
    state = eng._init_pool(2, jax.random.PRNGKey(0))
    text = eng._join_fn.lower(params, dp, state, jnp.zeros(32, jnp.int32),
                              jnp.int32(1), jnp.int32(0)).as_text(
                                  dialect="hlo")
    assert text.startswith("HloModule jit_join,")


@pytest.mark.parametrize("paged", [False, True])
def test_kv_tokens_attended_by_hand(setup, paged):
    """Autoregressive decoding emits one token a step, so a request of
    prompt P and budget N runs N - 1 live steps (its first token comes from
    prefill) over contexts P, P + 1, ..., P + N - 2."""
    cfg, params, dp, tree = setup
    shapes = [(9, 5), (21, 7)]
    kw = dict(max_len=MAX_LEN, use_speculative=False)
    eng = (PagedSpeculativeEngine(params, dp, cfg, tree, block_size=16, **kw)
           if paged else SpeculativeEngine(params, dp, cfg, tree, **kw))
    reqs = _requests(cfg, shapes)
    stats = eng.serve(reqs, max_batch=2)
    assert all(r.done and len(r.output) == n for r, (_, n) in zip(reqs,
                                                                 shapes))
    want = sum(p + j for p, n in shapes for j in range(n - 1))
    assert want == 4 * 9 + 6 + 6 * 21 + 15
    assert stats.kv_tokens_attended == want
