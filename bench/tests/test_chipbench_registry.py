"""The harness finds every configuration, mix and metric reader by the
name ``BENCHMARK.json`` gives, and the file keeps the contract's shape."""
import json
import os
import re
import subprocess
import sys

import pytest

from harness import cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# keys that name a width, which ``reduced`` may never hold
WIDTH = re.compile(r"(_dim$|_rank$|(?<!vocab)_size$|intermediate|latent|"
                   r"state|expan|per_tok)", re.I)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1].startswith("bench/")


def test_every_cell_resolves(bench):
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] == 1 and len(w["why"]) <= 200
        conf = cell.config_file(bench, w["config"])
        mix = cell.mix_file(w["traffic"])
        entry = [c for c in bench["configs"] if c["name"] == w["config"]]
        assert conf["reduced"] == entry[0]["reduced"]
        assert set(conf["reduced"]) <= set(conf["published"])
        for key in conf["reduced"]:
            assert not WIDTH.search(key), key
        assert {"max_batch", "max_len", "block_size", "prefill_chunk",
                "pool_tokens"} <= set(mix["engine"])


def test_every_metric_has_a_reader(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        if m["name"] != "setup_s":
            assert callable(cell.metric_reader(m["name"]))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        n = w["name"]
        e2e = [m["name"] for m in bench["end_to_end"]
               if n in m.get("workloads", [n])]
        pl = [m for m in bench["per_layer"] if n in m.get("workloads", [n])]
        assert "setup_s" in e2e and len(e2e) >= 2 and pl
        for m in pl:
            assert m["moves"] in e2e


def test_config_file_is_its_own(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]


def test_every_kind_resolves(bench):
    for c in bench["configs"]:
        conf = cell.config_file(bench, c["name"])
        kind = cell.kind_module(conf)
        assert os.path.dirname(kind.__file__) == cell.KINDS_DIR
        for name in ("check_program", "logits_at", "flops_per_live_row",
                     "tree_work"):
            assert callable(getattr(kind, name)), (conf["model"]["kind"],
                                                   name)


def test_run_exits_nonzero_without_a_tpu(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell_name = bench["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cell_name, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
