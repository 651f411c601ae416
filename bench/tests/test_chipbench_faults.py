"""A whole run on the CPU at a tiny size, without the look for a chip:
a sound program comes out correct, and ``correct`` comes out false for
the control (the fp8 reference in the program's place) and for each
fault a served cell can have, planted under the timed path.

The faults a training cell can have besides (half of the batch left out,
the exchange between chips left out) have no counterpart here: a served
cell on one chip has no batch mean and no exchange.
"""
import time

import numpy as np
import pytest

from harness import cell, check

# the tiny cell's limit: sound runs read under 0.01, the control 0.08 up
TINY_LIMIT = 0.03


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.launch.specs import tree_for
    cfg = get_config("minitron-4b").reduced()
    conf = cell.load_json(cell.os.path.join(cell.BENCH_DIR, "configs",
                                            "minitron-4b.json"))
    conf["model"].update(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        d_ff=cfg.d_ff, vocab_size=cfg.vocab_size)
    conf["draft"]["tree_nodes_per_depth"] = np.bincount(
        tree_for(cfg).depth).tolist()
    mix = {"loop": "open", "arrivals": "poisson", "rate_rps": 3.0,
           "schedule_seed": 0,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                      "min": 8, "max": 120},
           "output": {"dist": "uniform", "min": 8, "max": 24},
           "engine": {"max_batch": 4, "max_len": 256, "block_size": 16,
                      "prefill_chunk": 64, "pool_tokens": 512},
           "check": {"requests": 6, "gap_limit": TINY_LIMIT},
           "trace_s": 1.0}
    return cfg, conf, mix


def run_tiny(tiny, seed, after_check=None):
    cfg, conf, mix = tiny
    return cell.run({"name": "tiny", "chips": 1}, conf, mix, seed=seed,
                    seconds=3.0, trace=False, t_process=time.time(),
                    per_layer=[], end_to_end=[], cfg=cfg,
                    require_tpu=False, compile_cache=False,
                    after_check=after_check, log=lambda s: None)


def test_sound_run_is_correct_and_the_control_is_not(tiny):
    res, extra = run_tiny(tiny, 1, after_check=check.control_gap)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] > 30
    assert list(res["checks"])[0] == "logit_gap"
    assert extra["after_check"]["gap"] > TINY_LIMIT
    assert not check.control_decides(res["checks"], extra["after_check"])


@pytest.mark.parametrize("name,value,correct", [
    ("logit_gap", 0.5, False), ("logit_gap", 0.1, True),
    ("tokens_compared", 0, False), ("window_compiles", 1, False)])
def test_decide_holds_each_number_to_its_limit(name, value, correct):
    checks = {"logit_gap": {"value": 0.1, "limit": 0.3},
              "tokens_compared": {"value": 40, "limit": 1},
              "invalid_requests": {"value": 0, "limit": 0},
              "window_compiles": {"value": 0, "limit": 0}}
    checks[name] = dict(checks[name], value=value)
    assert check.decide(checks) is correct


def _state_unchanged(orig):
    def step(p, dp, cfg, tree, st, tbl, **kw):
        return orig(p, dp, cfg, tree, st, tbl, **kw)._replace(state=st)
    return step


def _token_altered(orig):
    def step(p, dp, cfg, tree, st, tbl, **kw):
        res = orig(p, dp, cfg, tree, st, tbl, **kw)
        V = cfg.vocab_size
        return res._replace(emitted=(res.emitted + 1) % V)
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_a_planted_fault_is_not_correct(tiny, monkeypatch, fault):
    import repro.serving.engine as engine
    monkeypatch.setattr(engine, "paged_spec_decode_step",
                        fault(engine.paged_spec_decode_step))
    res, _ = run_tiny(tiny, 2)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT
