"""The trace reduction on a small trace trimmed from one chip run
(``data/trace_minitron_chat.json``: a 3 s sub-window of minitron-chat on
one TPU v5 lite; of the operations only the tree kernel, the sorts and
the layer-scan loops are kept)."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from harness import derive
from harness import trace as tr
from work.tree_attn import OP_PATTERN

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_minitron_chat.json")


def planes():
    with open(DATA) as f:
        raw = json.load(f)["planes"]
    ev = lambda e: SimpleNamespace(name=e[0], start_ns=e[1], end_ns=e[2])
    return [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=l["name"], events=[ev(e) for e in l["events"]])
        for l in p["lines"]]) for p in raw]


@pytest.fixture(scope="module")
def dev():
    (dt,) = tr.reduce_planes(planes())
    return dt


def test_busy_union_by_painting(dev):
    ops = [e for p in planes() if p.name.startswith(tr.DEVICE_PREFIX)
           for l in p.lines if l.name == tr.OPS_LINE for e in l.events]
    lo = min(e.start_ns for e in ops) // 1000
    hi = max(e.end_ns for e in ops) // 1000 + 1
    paint = np.zeros(hi - lo, bool)
    for e in ops:
        paint[e.start_ns // 1000 - lo:-(-e.end_ns // 1000) - lo] = True
    # painting at 1 us rounds each interval out by under 2 us
    assert abs(paint.sum() * 1000 - dev.busy_ns) <= 2000 * len(
        dev.busy_intervals)
    assert dev.busy_ns <= dev.last_ns - dev.first_ns


def test_modules_and_kernel_calls(dev):
    assert len(dev.modules) == 46
    verify = [m for m in dev.modules[1:-1] if tr.has_op(m, OP_PATTERN)]
    assert len(verify) == 8
    assert all(len(tr.op_events(m, OP_PATTERN)) == 33 for m in verify)
    # the layer loop and sorts are not the kernel
    assert not any(tr.has_op(m, OP_PATTERN) for m in dev.modules
                   if m.name.startswith("jit_convert"))


def test_kernel_time_and_step_time(dev):
    ctx = SimpleNamespace(device=dev, kernel_pattern=OP_PATTERN)
    runs = derive._verify_runs(ctx)
    kernel_ns = sum(e - s for m in runs for _, s, e in
                    tr.op_events(m, OP_PATTERN))
    direct = sum(e[2] - e[1] for p in json.load(open(DATA))["planes"]
                 for l in p["lines"] for e in l["events"]
                 if e[0].startswith("%tree_attention_template")
                 and any(m.start <= e[1] < m.end for m in runs))
    assert kernel_ns == direct
    step = derive.verify_step_ms(ctx)
    assert 290.0 < step < 300.0
    assert 0.5 < kernel_ns / sum(m.dur for m in runs) < 0.65


def test_idle_share_and_gaps(dev):
    (span,) = [s for s in dev.host_spans if s[0] == "bench.traced_window"]
    lo, hi = span[1], span[2]
    busy = sum(min(e, hi) - max(s, lo) for s, e in dev.busy_intervals
               if e > lo and s < hi)
    idle = 1.0 - busy / (hi - lo)
    # the whole trace read 0.9% idle; the trimmed one has fewer operations
    assert 0.0 < idle < 0.10
    gaps = tr.idle_gaps(dev, lo, hi, top=3)
    assert len(gaps) == 3 and gaps[0][1] >= gaps[1][1] >= gaps[2][1]
    assert sum(g for _, g in tr.idle_gaps(dev, lo, hi, top=10**6)) == \
        (hi - lo) - busy


def test_op_base_and_top_ops():
    assert tr.op_base("%sort.2 = f32[16] sort(...)") == "sort"
    assert tr.op_base("%broadcast.152.clone = f32[8]") == "broadcast"
    assert tr.op_base("%tree_attention_template.31 = ...") == \
        "tree_attention_template"
    top = tr.top_ops({"%while.1 = x": 9, "%sort.2 = x": 3, "%sort.5 = x": 4,
                      "%fusion.3 = x": 5})
    assert top == [("sort", 7), ("fusion", 5)]


def test_idle_gaps_stay_inside_the_window():
    dt = tr.DeviceTrace(busy_ns=0, first_ns=0, last_ns=0, modules=[],
                        op_time={}, busy_intervals=[(0, 10), (20, 30),
                                                    (50, 60), (80, 90)],
                        host_spans=[("bench.request_arrives", 12, 14)])
    gaps = tr.idle_gaps(dt, 5, 55)
    assert sorted(g for _, g in gaps) == [10, 20]
    assert ("bench.request_arrives", 10) in gaps
