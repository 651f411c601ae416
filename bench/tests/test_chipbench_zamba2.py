"""The ``zamba2`` kind and the chain verify's two metrics.

A toy hybrid configuration whose files live only under ``data/``
(``toy-zamba2.json``, the on/off mix ``toy-zamba2-burst.json``) runs
whole through ``cell.run`` on the CPU with the program's reduced
zamba2-7b, reads ``correct``, and the fp8 control through the kind's
reference does not.  The counts are checked by hand and against the
program's parameter tree, and the readers on a synthetic trace.
"""
import json
import os
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from harness import cell, check, traffic
from harness import trace as tr
from metrics import ssd_verify_ms, ssd_verify_roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
# the toy's limit: sound runs read 0.039-0.092, the control 1.37-1.83
# (CPU, seeds 1, 2**33 + 1, 3)
TOY_LIMIT = 0.3


def conf(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    return cell.kind_module(conf(os.path.join(BENCH, "configs",
                                              "zamba2-7b.json")))


def test_toy_hybrid_runs_correct_and_the_control_does_not():
    from repro.configs import get_config
    c = conf(os.path.join(DATA, "toy-zamba2.json"))
    mix = traffic.load_mix(os.path.join(DATA, "toy-zamba2-burst.json"))
    assert mix["check"]["gap_limit"] == TOY_LIMIT
    res, extra = cell.run(
        {"name": "toy-zamba2.burst", "chips": 1}, c, mix, seed=2**33 + 1,
        seconds=3.0, trace=False, t_process=time.time(), per_layer=[],
        end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}],
        cfg=get_config("zamba2-7b").reduced(), require_tpu=False,
        compile_cache=False, after_check=check.control_gap,
        log=lambda s: None)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] > 30
    ctl = extra["after_check"]
    assert ctl["gap"] > TOY_LIMIT and ctl["flips"] > 0
    assert not check.control_decides(res["checks"], ctl)


def test_check_program_holds_the_file(kind):
    from repro.configs import get_config
    c = conf(os.path.join(BENCH, "configs", "zamba2-7b.json"))
    cfg = get_config("zamba2-7b")
    kind.check_program(cfg, c)
    import dataclasses
    with pytest.raises(SystemExit, match="hybrid_layer_ids"):
        kind.check_program(dataclasses.replace(
            cfg, hybrid_layer_ids=(6, 12, 17, 23)), c)
    bad = dict(c, draft=dict(c["draft"], tree_nodes_per_depth=[1, 2, 1, 1]))
    with pytest.raises(SystemExit, match="nodes by depth"):
        kind.check_program(cfg, bad)


def test_base_params_from_shapes(kind):
    """The counts behind ``flops_per_live_row`` against the program's own
    tree: every matrix (the norms, the depthwise conv and the Mamba2
    scalars aside), about 2.85e9 at zamba2-7b's widths."""
    from repro.configs import get_config
    from repro.models.model import init_params
    m = conf(os.path.join(BENCH, "configs", "zamba2-7b.json"))["model"]
    d, V = m["d_model"], m["vocab_size"]
    want = (m["n_layers"] * kind._mamba_params(m) + 2 * d * V
            + m["num_mem_blocks"] * kind._block_params(m)
            + len(m["hybrid_layer_ids"]) * kind._invocation_params(m))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                get_config("zamba2-7b")))
    mats = {"embed", "lm_head", "w_in", "w_out", "wq", "wk", "wv", "wo",
            "w_gate", "w_up", "w_down", "lora_a", "lora_b", "linear"}
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    got = sum(int(np.prod(s.shape)) for path, s in leaves
              if path[-1].key in mats)
    assert got == want
    assert 2.8e9 < want < 2.9e9


def test_ssd_verify_work_by_hand(kind):
    m = {"d_model": 4, "mamba_expand": 2, "mamba_headdim": 2,
         "mamba_d_state": 3, "mamba_ngroups": 2, "n_layers": 3}
    d = {"tree_nodes_per_depth": [1, 1]}
    # d_in 8, 4 heads, T 2, 3 layers: a state of 3 x 8 floats; verify per
    # token x 8 and B, C 2 x 6 (2 bytes), dt 4 floats, y 8 floats; commit
    # reads the state and x, B, dt again, and writes the state
    flops, nbytes = kind.ssd_verify_work(2, m, d)
    assert flops == 8 * 2 * 3 * 8 * 2 * 3
    state = 3 * 8 * 4
    verify = state + 2 * (2 * (8 + 12) + 4 * 4 + 4 * 8)
    commit = 2 * state + 2 * (2 * (8 + 6) + 4 * 4)
    assert nbytes == 2 * 3 * (verify + commit)
    # linear in the live rows, so the window's mean may be fractional
    f1, b1 = kind.ssd_verify_work(1.5, m, d)
    assert (f1, b1) == (flops * 0.75, nbytes * 0.75)


def test_ssd_verify_work_at_the_cell(kind):
    c = conf(os.path.join(BENCH, "configs", "zamba2-7b.json"))
    _, nbytes = kind.ssd_verify_work(16, c["model"], c["draft"])
    # 24 layers of 16 rows: three passes over 64 x 7168 float32 states
    # (2.11 GB) and the tokens' 0.11 GB
    assert 2.22e9 < nbytes < 2.24e9


def _ctx(modules, model, draft, steps=10, rows=40):
    dev = tr.DeviceTrace(busy_ns=0, first_ns=0, last_ns=0, modules=modules,
                         op_time={}, busy_intervals=[], host_spans=[])
    stats = lambda s, r: {"steps": s, "active_slot_steps": r}
    win = SimpleNamespace(trace={"stats_open": stats(0, 0),
                                 "stats_close": stats(steps, rows)})
    from work.tree_attn import OP_PATTERN
    return SimpleNamespace(device=dev, win=win, model=model, draft=draft,
                           kernel_pattern=OP_PATTERN,
                           peaks={"bf16_flops": 197e12,
                                  "hbm_bytes_per_s": 819e9})


def _run(start, ssd_calls, dur=1000):
    ops = [("%tree_attention_template.1 = bf16[...] custom-call()",
            start, start + 10)]
    for i in range(ssd_calls):
        s = start + 20 + i * (dur + 5)
        name = "commit" if i % 4 == 3 else "verify"
        ops.append((f"%ssd_chain_{name}.3 = (f32[...]) custom-call()", s,
                    s + dur))
    return tr.Module("jit_verify_step", start, start + 100_000, ops)


def test_readers_on_a_synthetic_trace():
    c = conf(os.path.join(BENCH, "configs", "zamba2-7b.json"))
    m, d = c["model"], c["draft"]
    chunk = tr.Module("jit_prefill_chunk", 0, 50_000,
                      [("%fusion.1 = x", 0, 40_000)])
    mods = [chunk] + [_run(100_000 * (i + 1), 32) for i in range(3)] + [chunk]
    ctx = _ctx(mods, m, d)
    # the first and last runs are left out (the trace may cut them)
    assert ssd_verify_ms.read(ctx) == pytest.approx(32 * 1000 * 1e-6)
    kind = cell.kind_module(c)
    flops, nbytes = kind.ssd_verify_work(4.0, m, d)
    least = max(flops / 197e12, nbytes / 819e9)
    want = 100.0 * 2 * least / (64 * 1000 * 1e-9)
    assert ssd_verify_roofline.read(ctx) == pytest.approx(want)


def test_readers_read_absent_without_the_kernel():
    c = conf(os.path.join(BENCH, "configs", "zamba2-7b.json"))
    m, d = c["model"], c["draft"]
    mods = [_run(100_000 * (i + 1), 0) for i in range(4)]
    ctx = _ctx(mods, m, d)
    assert ssd_verify_ms.read(ctx) is None
    assert ssd_verify_roofline.read(ctx) is None
    # no trace at all (a CPU run), or no step in the window
    assert ssd_verify_ms.read(_ctx([], m, d)) is None
    ctx = _ctx([_run(100_000 * (i + 1), 32) for i in range(4)], m, d,
               steps=0, rows=0)
    assert ssd_verify_roofline.read(ctx) is None
    # a kind that counts no such work
    ctx = _ctx([_run(100_000 * (i + 1), 32) for i in range(4)],
               dict(m, kind="dense"), d)
    assert ssd_verify_ms.read(ctx) is not None
    assert ssd_verify_roofline.read(ctx) is None
