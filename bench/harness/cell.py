"""One run of one cell: set-up, the measured window, the check, the result.

Set-up (counted in ``setup_s``, from process start to the window's open):
weights drawn from the seed on the device, the engine built through the
program's own ``build_engine``, and one warm-up ``serve`` of queued
requests through the engine's own warm-up, sized so that every program
the window can run is compiled: the longest prompt the mix can send plus
its output (which is what a preempted request resumes with), so every
chunk view up to the row's capacity, and the verify step.

What depends on the model's kind (the check of the program's registered
configuration, the plain reference, the verify step's FLOPs and the tree
kernel's work) comes from ``bench/kinds/<model.kind>.py``, so a
configuration joins the benchmark with new files alone: its file under
``bench/configs/``, its kind's module where the kind is new, its mix
under ``bench/traffic/``, and entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from harness import check, derive, traffic
from harness import trace as tr
from harness.driver import WINDOW_SPAN, Source, run_window
from harness.peaks import peaks_for

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
KINDS_DIR = os.path.join(BENCH_DIR, "kinds")


class NoChip(SystemExit):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_file(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def mix_file(name: str) -> dict:
    return traffic.load_mix(os.path.join(BENCH_DIR, "traffic",
                                         name + ".json"))


def kind_module(conf: dict):
    """The module of the configuration's ``model.kind``:
    ``bench/kinds/<kind>.py`` (``kinds/dense.py`` says what it provides)."""
    kind = conf["model"]["kind"]
    path = os.path.join(KINDS_DIR, kind + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"configuration {conf.get('name')!r} has model.kind "
                         f"{kind!r}, but {path} is missing")
    return load_module(path, "bench_kind_" + kind.replace(".", "_"))


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_")).read


@dataclass
class RunContext:
    win: object
    seconds: float
    peaks: dict
    model: dict
    draft: dict
    device: Optional[tr.DeviceTrace] = None
    busy_ns: int = 0
    window_ns: int = 0
    kernel_pattern: str = ""
    flops_per_live_row: int = 0
    tree_work: object = None
    extra: dict = field(default_factory=dict)


def _ms(t, due, close):
    """Milliseconds from ``due`` to ``t``, or "-" where ``t`` is after the
    close or never came."""
    return "-" if t is None or t > close else f"{1e3 * (t - due):.0f}"


def warm_requests(draft: dict, mix: dict, vocab: int, Request):
    """Queued requests whose prefill covers every chunk view the window
    can use: one as long as the longest request can become (prompt plus
    output, the context a preempted request resumes with), and one of
    the shortest prompt.  A row also holds the verify step's tree and
    the tokens of the step still in flight (at most the tree's depth)."""
    e = mix["engine"]
    nodes = draft["tree_nodes_per_depth"]
    scratch = sum(nodes) + len(nodes)
    longest = min(traffic.longest_context(mix),
                  e["max_len"] - scratch - e["prefill_chunk"])
    rs = np.random.default_rng(0)
    out = []
    for n in (longest, mix["prompt"]["min"]):
        out.append(Request(prompt=rs.integers(0, vocab, n, dtype=np.int32),
                           max_new_tokens=2))
    return out


def run(cell: dict, conf: dict, mix: dict, *, seed: int, seconds: float,
        trace: bool, t_process: float, per_layer: list, end_to_end: list,
        cfg=None, require_tpu: bool = True, compile_cache: bool = True,
        after_check=None, log=print):
    """One run; returns the result object (the last line's contents) and
    a dict of extras: the run's ``RunContext`` (``ctx``), and what
    ``after_check(kind, params, model, picked, pad_to)`` returned (the
    control's reading, read on the same requests while the weights are
    still there; ``kind`` is the configuration's ``kind_module``)."""
    kind = kind_module(conf)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        raise NoChip(f"[bench] no TPU for this cell: JAX found "
                     f"platform={dev.platform} kind={dev.device_kind} "
                     f"count={len(devices)}, the cell asks for "
                     f"{cell['chips']} TPU chip(s)")
    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.launch.serve import build_engine
    from repro.models.model import init_params
    from repro.runtime_env import use_compilation_cache
    from repro.serving.engine import Request

    cache_dir = use_compilation_cache() if compile_cache else None
    n_compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **kw: n_compiles.__setitem__(0, n_compiles[0] + 1)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    peaks = peaks_for(dev.device_kind) if require_tpu else {
        "bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    log(f"[bench] cell={cell['name']} seed={seed} seconds={seconds} "
        f"trace={int(trace)} device={dev.platform}/{dev.device_kind} "
        f"x{len(devices)} jax={jax.__version__} cache={cache_dir}")

    cfg = cfg or get_config(conf["program_config"])
    kind.check_program(cfg, conf)
    e = mix["engine"]
    vocab = conf["model"]["vocab_size"]

    # -- set-up ----------------------------------------------------------
    from harness.weights import make_weights
    t = time.time()
    shapes = jax.eval_shape(lambda: (
        init_params(jax.random.PRNGKey(0), cfg),
        init_draft_params(jax.random.PRNGKey(0), cfg)))
    params, dparams = make_weights(shapes, seed)
    jax.block_until_ready((params, dparams))
    log(f"[bench] weights: {sum(x.nbytes for x in jax.tree.leaves((params, dparams)))} "
        f"bytes in {time.time() - t:.3f} s")
    pool_frac = (e["pool_tokens"] + 0.5) / (e["max_batch"] * e["max_len"])
    eng = build_engine(cfg, params, dparams, engine="paged",
                       max_batch=e["max_batch"], max_len=e["max_len"],
                       block_size=e["block_size"],
                       prefill_chunk=e["prefill_chunk"],
                       pool_frac=pool_frac)
    t = time.time()
    warm = warm_requests(conf["draft"], mix, vocab, Request)
    eng.serve(warm, max_batch=e["max_batch"])
    sync = jax.jit(lambda: jnp.zeros((), jnp.int32) + 1)
    sync().block_until_ready()
    ms = dev.memory_stats() or {}
    log(f"[bench] memory after warm-up: in use {ms.get('bytes_in_use')} "
        f"peak {ms.get('peak_bytes_in_use')} limit {ms.get('bytes_limit')}")
    log(f"[bench] warm-up: {time.time() - t:.3f} s, prompts "
        f"{[len(r.prompt) for r in warm]}, compiles so far {n_compiles[0]}, "
        f"pool {eng.stats.pool_tokens} tokens")
    compiles_before = n_compiles[0]

    def make_request(i, p, o):
        return Request(prompt=traffic.prompt_tokens(seed, i, p, vocab),
                       max_new_tokens=o)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    hooks = None
    if trace:
        hooks = (lambda: jax.profiler.start_trace(log_dir),
                 jax.profiler.stop_trace)
    src = Source(mix, seconds, make_request, eng.stats, hooks)
    setup_s = time.time() - t_process

    # -- the window ----------------------------------------------------------
    win = run_window(eng, src, e["max_batch"])
    sync().block_until_ready()
    window_compiles = n_compiles[0] - compiles_before
    peak = dev.memory_stats().get("peak_bytes_in_use") \
        if dev.memory_stats() else None
    recs = win.records
    log(f"[bench] window: {len(recs)} requests due, {win.done_at_close} "
        f"finished, {win.tokens_at_close} tokens, compiles in window "
        f"{window_compiles}, engine counters "
        f"{ {k: win.stats_close[k] - win.stats_open[k] for k in win.stats_close} }")

    from work.tree_attn import OP_PATTERN
    m, d = conf["model"], conf["draft"]
    T = sum(d["tree_nodes_per_depth"])
    T_pad = -(-T // 8) * 8
    ctx = RunContext(
        win=win, seconds=seconds, peaks=peaks, model=m, draft=d,
        kernel_pattern=OP_PATTERN,
        flops_per_live_row=kind.flops_per_live_row(m, d),
        tree_work=lambda cached: kind.tree_work(cached, T_pad, m))
    breakdown = None
    if trace:
        t = time.time()
        devs = tr.load(log_dir)
        log(f"[bench] trace read in {time.time() - t:.3f} s")
        if devs:
            dt = devs[0]
            ctx.device = dt
            t_ = win.trace
            host_ns = int(1e9 * (t_["t_close"] - t_["t_open"]))
            spans = [s for s in dt.host_spans if s[0] == WINDOW_SPAN]
            if spans:
                lo, hi = spans[0][1], spans[0][2]
            else:
                lo, hi = dt.first_ns, dt.first_ns + host_ns
            clipped = tr.union([(max(s, lo), min(e_, hi))
                                for s, e_ in dt.busy_intervals
                                if e_ > lo and s < hi])
            ctx.busy_ns = sum(e_ - s for s, e_ in clipped)
            ctx.window_ns = hi - lo
            breakdown = {
                "device_ops": [[n, v * 1e-9]
                               for n, v in tr.top_ops(dt.op_time)],
                "idle_gaps": [[n, v * 1e-9]
                              for n, v in tr.idle_gaps(dt, lo, hi)]}
        shutil.rmtree(log_dir, ignore_errors=True)

    # -- free the program, then the check ------------------------------------
    del eng, src
    dparams = None
    gc.collect()
    done = check.finished(win)
    bad = check.validity_failures(recs, vocab)
    picked = check.sample(done, seed, int(mix["check"]["requests"]),
                          [r for r in recs if not r.req.done])
    pad_to = -(-traffic.longest_context(mix) // 256) * 256
    t = time.time()
    got = check.served_gap(kind, params, m, picked, pad_to)
    limit = float(mix["check"]["gap_limit"])
    log(f"[bench] check: {len(picked)} requests ({len(done)} finished), "
        f"{got['tokens']} served tokens, per-request widest gaps "
        f"{got['per_request']}, {time.time() - t:.3f} s")
    checks = {
        "logit_gap": {"value": got["gap"], "limit": limit},
        "tokens_compared": {"value": got["tokens"], "limit": 1},
        "invalid_requests": {"value": len(bad), "limit": 0},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    if after_check is not None:
        ctx.extra["after_check"] = after_check(kind, params, m, picked,
                                               pad_to)
    correct = check.decide(checks)
    for b in bad[:5]:
        log(f"[bench] invalid: {b}")

    # -- metrics -------------------------------------------------------------
    ttft = derive.ttft_samples(ctx)
    tpot = derive.tpot_samples(ctx)
    log("[bench] requests (due s, join wait ms, ttft ms, tokens): " + " ".join(
        f"({r.due - win.t0:.2f},{_ms(r.req.t_join, r.due, win.t_close)},"
        f"{_ms(r.req.t_first_token, r.due, win.t_close)},"
        f"{win.at_close[r.index][0]})" for r in recs))
    log(f"[bench] samples: ttft n={len(ttft)} tpot n={len(tpot)} "
        f"gen_lag_p99_ms={derive.gen_lag_ms(ctx)} "
        f"tokens/row-step={derive.tokens_per_row_step(ctx)} "
        f"peak_bytes={peak} setup_s={setup_s}")
    names = per_layer if trace else end_to_end
    metrics = {}
    for spec in names:
        if spec["name"] == "setup_s":
            val = setup_s
        else:
            val = metric_reader(spec["name"])(ctx)
        if val is not None:
            metrics[spec["name"]] = {"value": float(val),
                                     "unit": spec["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = ctx.busy_ns * 1e-9
        device["window_s"] = ctx.window_ns * 1e-9
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": len(bad), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    ctx.extra["ctx"] = ctx
    return result, ctx.extra
