"""A configuration joins the benchmark with new files alone: a test
configuration whose files all live under ``data/`` (its file
``toy-dense.json``, its kind's module ``toy_dense.py``, its on/off mix
``toy-burst.json``) runs whole through ``cell.run`` on the CPU and reads
``correct``, and the control through its kind's reference does not.
No harness file names it: the harness finds the kind by ``model.kind``."""
import os
import time

import pytest

from harness import cell, check, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the toy's limit: sound runs read 0-0.004, the control 0.117-0.194
# (CPU, seeds 1, 2**33 + 1, 3)
TOY_LIMIT = 0.03


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(cell, "KINDS_DIR", DATA)
    conf = cell.load_json(os.path.join(DATA, "toy-dense.json"))
    mix = traffic.load_mix(os.path.join(DATA, "toy-burst.json"))
    return conf, mix


def run_toy(conf, mix, after_check=None):
    return cell.run({"name": "toy-dense.burst", "chips": 1}, conf, mix,
                    seed=2**33 + 1, seconds=3.0, trace=False,
                    t_process=time.time(), per_layer=[],
                    end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                                {"name": "setup_s", "unit": "s"}],
                    require_tpu=False, compile_cache=False,
                    after_check=after_check, log=lambda s: None)


def test_a_configuration_in_new_files_alone_runs_correct(toy):
    conf, mix = toy
    assert mix["check"]["gap_limit"] == TOY_LIMIT
    res, extra = run_toy(conf, mix, after_check=check.control_gap)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] > 30
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    # the kind's own counts reach the run
    kind = cell.kind_module(conf)
    ctx = extra["ctx"]
    assert ctx.flops_per_live_row == kind.flops_per_live_row(
        conf["model"], conf["draft"])
    assert ctx.tree_work([10, 20]) == kind.tree_work([10, 20], 16,
                                                     conf["model"])
    # every arrival fell inside an on-phase
    period = mix["on_s"] + mix["off_s"]
    assert all((r.due - ctx.win.t0) % period < mix["on_s"]
               for r in ctx.win.records)
    ctl = extra["after_check"]
    assert ctl["gap"] > TOY_LIMIT
    assert not check.control_decides(res["checks"], ctl)


def test_a_kind_without_a_module_fails_at_once(toy):
    conf, mix = toy
    conf = dict(conf, model=dict(conf["model"], kind="no_such_kind"))
    t = time.time()
    with pytest.raises(SystemExit, match=r"no_such_kind\.py is missing"):
        run_toy(conf, mix)
    assert time.time() - t < 5.0
