"""Per-layer metric ``ssd_verify_roofline``: the least time of the Mamba2
chain's work over its kernels' device time, in %.

The work of one verify step is the kind's ``ssd_verify_work(live_rows,
model, draft)`` at the traced window's mean live rows (the deltas of
``active_slot_steps`` over ``steps``): every Mamba2 layer's verify pass
and commit replay.  The least time is max(FLOPs / peak, bytes /
bandwidth), times the window's verify-step runs, over the device time of
their ``ssd_chain`` calls.  None, read as absent and never as 0, where
no such call ran, the window ran no step, or the configuration's kind
counts no such work.
"""
from __future__ import annotations

from harness import cell
from metrics.ssd_verify_ms import calls


def read(ctx):
    runs, events = calls(ctx)
    t = ctx.win.trace
    steps = t["stats_close"]["steps"] - t["stats_open"]["steps"]
    rows = (t["stats_close"]["active_slot_steps"]
            - t["stats_open"]["active_slot_steps"])
    work = getattr(cell.kind_module({"model": ctx.model}), "ssd_verify_work",
                   None)
    ns = sum(e - s for _, s, e in events)
    if not (events and steps and rows and ns and work):
        return None
    flops, nbytes = work(rows / steps, ctx.model, ctx.draft)
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * len(runs) * least / (1e-9 * ns)
