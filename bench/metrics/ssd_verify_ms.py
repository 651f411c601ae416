"""Per-layer metric ``ssd_verify_ms``: device time of the Mamba2 chain
kernels (``repro.kernels.ssd_chain``) per verify-step run of the traced
window: the verify pass (``%ssd_chain_verify``, one call a Mamba2 layer)
and the commit's replay (``%ssd_chain_commit``, one call a group of
layers).

The calls are those inside the runs that hold the paged tree kernel
(``harness.derive``'s verify runs).  None, read as absent, where no such
operation ran: a program without the kernels, or a window without a
verify step.
"""
from __future__ import annotations

from harness import derive
from harness import trace as tr

# the kernels' operations in the trace's ``XLA Ops`` line: their HLO
# instructions, named after the kernels
OP_PATTERN = r"^%ssd_chain_(verify|commit)(\.\d+)? "


def calls(ctx):
    """(verify-step runs, the kernels' (name, start, end) events in them)."""
    runs = derive._verify_runs(ctx)
    return runs, [o for m in runs for o in tr.op_events(m, OP_PATTERN)]


def read(ctx):
    runs, events = calls(ctx)
    if not events:
        return None
    return 1e-6 * sum(e - s for _, s, e in events) / len(runs)
