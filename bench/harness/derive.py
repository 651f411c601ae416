"""Numbers read from one run: the end-to-end metrics and the readings
behind each per-layer metric.  ``bench/metrics/<name>.py`` picks one of
these by name; a reading that has nothing to read returns None.

``ctx`` is ``cell.RunContext``: the window (requests with their due,
join, first-token and done stamps; engine counters at the window's open
and close; the traced sub-window's counters and times), the reduced
device trace, the configuration and the peaks.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

from harness import trace as tr


def _pct(values: List[float], p: float) -> Optional[float]:
    """The p-th percentile by ``statistics.quantiles`` (exclusive method,
    n=100); None for fewer than two samples."""
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=100)[int(p) - 1]


# -- end to end ------------------------------------------------------------

def tokens_per_s(ctx) -> float:
    """Output tokens delivered by the window's close, first tokens
    included, over the window's seconds."""
    return ctx.win.tokens_at_close / ctx.seconds


def ttft_samples(ctx) -> List[float]:
    """First-token time minus due time of every request due in the window;
    a request with no token at the close counts the time it has waited."""
    out = []
    for r in ctx.win.records:
        t = r.req.t_first_token
        if t is None or t > ctx.win.t_close:
            t = ctx.win.t_close
        out.append(t - r.due)
    return out


def ttft_p90_ms(ctx) -> Optional[float]:
    v = _pct(ttft_samples(ctx), 90)
    return None if v is None else 1e3 * v


def tpot_samples(ctx) -> List[float]:
    """Per request with two tokens or more by the close: the time from its
    first token to its last one before the close, over the tokens after
    the first.  Requests still running at the close count, so long
    outputs are not left out of the tail."""
    out = []
    for n, t_last, t_first in ctx.win.at_close.values():
        if n > 1 and t_last is not None and t_first is not None:
            out.append((t_last - t_first) / (n - 1))
    return out


def tpot_p90_ms(ctx) -> Optional[float]:
    v = _pct(tpot_samples(ctx), 90)
    return None if v is None else 1e3 * v


# -- load generator and scheduler -------------------------------------------

def gen_lag_ms(ctx) -> Optional[float]:
    v = _pct([r.yielded - r.due for r in ctx.win.records], 99)
    return None if v is None else 1e3 * v


def queue_wait_p90_ms(ctx) -> Optional[float]:
    waits = []
    for r in ctx.win.records:
        t = r.req.t_join
        if t is None or t > ctx.win.t_close:
            t = ctx.win.t_close
        waits.append(t - r.due)
    v = _pct(waits, 90)
    return None if v is None else 1e3 * v


def _delta(ctx, key) -> int:
    return ctx.win.stats_close[key] - ctx.win.stats_open[key]


def tokens_per_row_step(ctx) -> Optional[float]:
    rows = _delta(ctx, "active_slot_steps")
    return _delta(ctx, "tokens") / rows if rows else None


def preempt_share(ctx) -> Optional[float]:
    done = ctx.win.done_at_close
    return 100.0 * _delta(ctx, "preemptions") / done if done else None


# -- device trace -----------------------------------------------------------

def _whole_runs(ctx):
    """Program runs of the trace without its first and last, which the
    trace's start and stop may cut."""
    return ctx.device.modules[1:-1] if ctx.device else []


def _verify_runs(ctx):
    return [m for m in _whole_runs(ctx) if tr.has_op(m, ctx.kernel_pattern)]


def _other_runs(ctx):
    return [m for m in _whole_runs(ctx)
            if not tr.has_op(m, ctx.kernel_pattern)]


def verify_step_ms(ctx) -> Optional[float]:
    runs = _verify_runs(ctx)
    if not runs:
        return None
    return 1e-6 * sum(m.dur for m in runs) / len(runs)


def prefill_chunk_ms(ctx) -> Optional[float]:
    """Mean device time of a prefill chunk: the traced window's program
    runs that hold no tree kernel, longest first, as many as the engine
    counted chunks in that window (the rest are small copies)."""
    n = (ctx.win.trace["stats_close"]["prefill_chunks"]
         - ctx.win.trace["stats_open"]["prefill_chunks"])
    runs = sorted(_other_runs(ctx), key=lambda m: -m.dur)[:max(n, 0)]
    if not runs:
        return None
    return 1e-6 * sum(m.dur for m in runs) / len(runs)


def device_idle_share(ctx) -> Optional[float]:
    if not ctx.device or not ctx.window_ns:
        return None
    return 100.0 * (1.0 - ctx.busy_ns / ctx.window_ns)


def step_mfu(ctx) -> Optional[float]:
    runs = _verify_runs(ctx)
    t = ctx.win.trace
    steps = t["stats_close"]["steps"] - t["stats_open"]["steps"]
    rows = (t["stats_close"]["active_slot_steps"]
            - t["stats_open"]["active_slot_steps"])
    if not runs or not steps or not rows:
        return None
    flops = len(runs) * (rows / steps) * ctx.flops_per_live_row
    secs = 1e-9 * sum(m.dur for m in runs)
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops"])


def tree_attn_roofline(ctx) -> Optional[float]:
    """Least time of the paged tree kernel's mean call over its device
    time, in %, for the work the engine counted in the traced window.

    The calls are the kernel's in the window's verify-step runs.  The mean
    step's work is the window's: the delta of ``kv_tokens_attended``
    (each live row's prompt + output - 1 when its step ran) cached tokens
    over the delta of ``active_slot_steps`` live row-steps, in the delta
    of ``steps`` steps; ``ctx.tree_work`` (the kind's ``tree_work``)
    gives one call's (flops, bytes) for it, and reads only the sum and
    the count of the rows it is given.  The least time is
    max(flops / peak, bytes / bandwidth).  None where the engine keeps no
    ``kv_tokens_attended`` or the window ran no call."""
    t = ctx.win.trace
    if "kv_tokens_attended" not in t.get("stats_close", {}):
        return None
    d = {k: t["stats_close"][k] - t["stats_open"][k]
         for k in ("kv_tokens_attended", "active_slot_steps", "steps")}
    calls = [o for m in _verify_runs(ctx)
             for o in tr.op_events(m, ctx.kernel_pattern)]
    calls_ns = sum(e - s for _, s, e in calls)
    rows, steps = d["active_slot_steps"], d["steps"]
    if not (calls_ns and rows and steps):
        return None
    flops, nbytes = ctx.tree_work([d["kv_tokens_attended"]]
                                  + [0] * (rows - 1))
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"]) / steps
    return 100.0 * len(calls) * least / (1e-9 * calls_ns)
