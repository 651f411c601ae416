"""The readings of the engine's own instrumentation (``harness.engine_trace``)
on a small trace trimmed from one chip run (``data/trace_minitron_chat_engine.json``,
its ``source`` says which; of the operations only the tree kernel, the sorts
and the layer-scan loops are kept, with the ``op_name`` of each from the
verify step's compiled text), and on a CPU trace of a tiny paged engine."""
import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from harness import derive, driver
from harness import engine_trace as et
from harness import trace as tr
from work.tree_attn import OP_PATTERN, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_minitron_chat_engine.json")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def doc():
    with open(DATA) as f:
        return json.load(f)


def _planes(raw):
    ev = lambda e: SimpleNamespace(
        name=e[0], start_ns=e[1], end_ns=e[2],
        stats=list(e[3].items()) if len(e) > 3 else [])
    return [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=l["name"], events=[ev(e) for e in l["events"]])
        for l in p["lines"]]) for p in raw]


@pytest.fixture(scope="module")
def chip(doc):
    planes = _planes(doc["planes"])
    (dev,) = tr.reduce_planes(planes)
    spans = et.engine_spans(planes)
    (window,) = [s for s in dev.host_spans if s[0] == "bench.traced_window"]
    verify = [m for m in dev.modules[1:-1]
              if m.name.startswith("jit_verify_step(")]
    return SimpleNamespace(dev=dev, spans=spans, lo=window[1], hi=window[2],
                           verify=verify)


def test_programs_are_named(chip):
    names = {m.name.split("(")[0] for m in chip.dev.modules}
    assert {"jit_verify_step", "jit_prefill_chunk_final"} <= names
    assert not any(n.startswith("jit__lambda") for n in names)
    # the name and the kernel agree on which runs are the verify step
    ctx = SimpleNamespace(device=chip.dev, kernel_pattern=OP_PATTERN)
    assert chip.verify == derive._verify_runs(ctx)


def test_spans_nest_in_iterations(chip):
    iters = [s for s in chip.spans if s[0] == et.ITERATION]
    assert len(iters) >= 30
    # a span open when the trace started or stopped is not recorded, so
    # only the spans between the first and the last loop pass are checked
    first, last = min(i[1] for i in iters), max(i[2] for i in iters)
    for name in ("engine.poll", "engine.admit", "engine.alloc",
                 "engine.dispatch", "engine.read", "engine.harvest"):
        inner = [s for s in chip.spans if s[0] == name
                 and first <= s[1] and s[2] <= last]
        assert inner, name
        assert all(any(i[1] <= s[1] and s[2] <= i[2] for i in iters)
                   for s in inner), name
    for _, _, _, args in (s for s in chip.spans
                          if s[0] == "engine.prefill_chunk"):
        assert {"rid", "start", "final"} <= set(args)


def test_host_loop(chip):
    iters = [s for s in chip.spans if s[0] == et.ITERATION
             and chip.lo <= s[1] and s[2] <= chip.hi]
    host = et.host_loop_ns(chip.spans, chip.lo, chip.hi)
    assert len(host) == len(iters)
    assert all(0 < h < e - s for h, (_, s, e, _) in zip(host, iters))
    # the step's blocking read takes nearly all of each loop pass
    assert 0.3 < 1e-6 * sum(host) / len(host) < 10.0


def test_host_loop_by_hand():
    spans = [("engine.iteration", 0, 100, {}), ("engine.read", 10, 40, {}),
             ("engine.read", 30, 60, {}), ("engine.iteration", 100, 150, {}),
             ("engine.dispatch", 105, 110, {}),
             ("engine.iteration", 150, 300, {}),   # outside [0, 200]
             ("engine.read", 160, 290, {})]
    assert et.host_loop_ns(spans, 0, 200) == [100 - 50, 50]


def test_gaps_are_named_by_engine_spans(chip):
    gaps = []
    prev = chip.lo
    for s, e in chip.dev.busy_intervals:
        if chip.lo < s < chip.hi and s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    # (the trimmed trace keeps few operations, so it has many such gaps;
    # the spans open when the trace stopped were not recorded)
    last = max(e for n, _, e, _ in chip.spans if n == et.READ)
    long = sorted((g for g in gaps if g[1] - g[0] > 1_000_000
                   and g[1] <= last), key=lambda g: g[0] - g[1])
    assert long
    assert all(et.gap_names(chip.spans, s, e) for s, e in long)
    # the longest opens the window: the host waits in a step's read while
    # the device trace has not started yet, so it is no idle time
    assert long[0][0] == chip.lo
    assert "engine.read" in et.gap_names(chip.spans, *long[0])
    spans = [("engine.iteration", 0, 100, {}), ("engine.read", 10, 40, {})]
    assert et.gap_names(spans, 20, 30) == "engine.read"
    assert et.gap_names(spans, 50, 60) == "engine.iteration"
    assert et.gap_names(spans, 200, 210) is None


def test_op_scopes_and_phases(doc, chip):
    text = ('  %sort.2 = f32[4] sort(f32[4] %a), dimensions={0}, '
            'metadata={op_name="jit(verify_step)/draft/top_k:" '
            'source_file="x"}\n'
            '  ROOT %tuple = (f32[4]) tuple(%sort.2)\n'
            '  %add.1 = f32[] add(%p, %q), '
            'metadata={op_name="jit(verify_step)/jit(_where)/select"}\n')
    scopes = et.op_scopes(text)
    assert scopes == {"sort.2": "jit(verify_step)/draft/top_k:",
                      "add.1": "jit(verify_step)/jit(_where)/select"}
    assert [et.phase(scopes[k]) for k in ("sort.2", "add.1")] == ["draft", ""]
    # each verify run: 32 tree-kernel calls in the base forward, one in the
    # Hydra++ prefix layer; the draft's sorts are the draft's
    for m in chip.verify:
        calls = [et.phase(doc["scopes"][o[0].split(" = ")[0][1:]])
                 for o in tr.op_events(m, OP_PATTERN)]
        assert calls.count("verify") == 32 and calls.count("draft_prefix") == 1
        sorts = [o for o in m.ops if tr.op_base(o[0]) == "sort"]
        assert sorts and all(
            et.phase(doc["scopes"][o[0].split(" = ")[0][1:]]) == "draft"
            for o in sorts)


def test_draft_ms(doc, chip):
    draft = et.draft_ms(chip.verify, doc["scopes"])
    ctx = SimpleNamespace(device=chip.dev, kernel_pattern=OP_PATTERN)
    step = derive.verify_step_ms(ctx)
    assert 0 < draft < step
    # of the kept operations, the draft's are its sorts and its one kernel
    by_hand = sum(e - s for m in chip.verify for n, s, e in m.ops
                  if tr.op_base(n) == "sort") + sum(
        e - s for m in chip.verify
        for n, s, e in tr.op_events(m, OP_PATTERN)[-1:])
    assert draft == pytest.approx(1e-6 * by_hand / len(chip.verify))
    assert et.draft_ms([], doc["scopes"]) is None


def _roofline_ctx(doc, chip, **stats):
    t = dict(doc["trace"])
    t["stats_close"] = dict(t["stats_close"], **stats)
    return SimpleNamespace(
        win=SimpleNamespace(trace=t), device=chip.dev,
        kernel_pattern=OP_PATTERN, peaks=PEAKS,
        tree_work=lambda cached: work(cached, 16, 24, 8, 128))


def test_live_roofline_not_below_the_certain_bound(doc, chip):
    live = derive.tree_attn_roofline(_roofline_ctx(doc, chip))
    # the certain-work bound on the same calls: the prompts of the
    # requests live over the whole traced window, each step
    t = doc["trace"]
    certain = [r["prompt"] for r in doc["requests"]
               if r["first"] < t["t_open"]
               and (r["done"] is None or r["done"] > t["t_close"])]
    assert certain
    calls = [o for m in chip.verify for o in tr.op_events(m, OP_PATTERN)]
    flops, nbytes = work(certain, 16, 24, 8, 128)
    least = max(flops / PEAKS["bf16_flops"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    bound = 100.0 * len(calls) * least / (1e-9 * sum(e - s for _, s, e
                                                      in calls))
    assert 0 < bound <= live < 100


def test_tree_attn_roofline_without_the_counter_or_the_work(doc, chip):
    ctx = _roofline_ctx(doc, chip)
    for snap in ("stats_open", "stats_close"):
        ctx.win.trace[snap] = {k: v for k, v in ctx.win.trace[snap].items()
                               if k != "kv_tokens_attended"}
    assert derive.tree_attn_roofline(ctx) is None
    # no step in the window, or no device trace: nothing to read
    steps = doc["trace"]["stats_open"]["steps"]
    assert derive.tree_attn_roofline(_roofline_ctx(doc, chip,
                                                   steps=steps)) is None
    ctx = _roofline_ctx(doc, chip)
    ctx.device = None
    assert derive.tree_attn_roofline(ctx) is None


def test_stats_snapshot_leaves_out_a_counter_the_engine_lacks():
    from repro.serving.engine import EngineStats
    assert set(driver.stats_snapshot(EngineStats())) == set(driver.STAT_KEYS)
    # an engine from before the counter: absent, never read as 0
    old = SimpleNamespace(**{k: 1 for k in driver.STAT_KEYS
                             if k != "kv_tokens_attended"})
    assert "kv_tokens_attended" not in driver.stats_snapshot(old)


def test_cpu_trace_of_a_paged_engine(tmp_path):
    """The readings hold on a real trace of the program (CPU, tiny)."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.core.trees import default_tree
    from repro.models.model import init_params
    from repro.serving.engine import PagedSpeculativeEngine, Request

    cfg = dataclasses.replace(get_config("vicuna-tiny"), dtype="float32")
    key = jax.random.PRNGKey(0)
    eng = PagedSpeculativeEngine(
        init_params(key, cfg), init_draft_params(key, cfg), cfg,
        default_tree(8, 2, 3), max_len=128, block_size=16, prefill_chunk=16)
    rs = np.random.RandomState(0)
    reqs = lambda: [Request(prompt=rs.randint(0, cfg.vocab_size, n).astype(
        np.int32), max_new_tokens=4) for n in (30, 12)]
    eng.serve(reqs(), max_batch=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(reqs(), max_batch=2, warmup=False)
    planes = ProfileData.from_file(tr.find_xplane(str(tmp_path))).planes
    spans = et.engine_spans(planes)
    host = et.host_loop_ns(spans, 0, 2**62)
    assert host and all(h > 0 for h in host)
    chunks = [a for n, _, _, a in spans if n == "engine.prefill_chunk"]
    assert [a["final"] for a in chunks].count(True) == 2
