"""Readings of the serving program's own instrumentation in a profiler trace.

The engine (``repro.serving.engine``, DESIGN.md §7) names its programs
(``jit_verify_step``, ``jit_join``, ``jit_prefill_chunk``,
``jit_prefill_chunk_final``), scopes the verify step's phases (``draft``,
``verify``, ``accept``, ``commit``, ``draft_prefix``) and writes host spans
``engine.*`` on the trace's clock, each loop pass one ``engine.iteration``.
``harness.trace`` keeps neither the engine's spans nor the op scopes, so a
run does not read these yet (PERF.md §7 names the edits that wire them).

    engine_spans   the ``engine.*`` host spans with their arguments
    host_loop_ns   per loop pass: its time less what its ``engine.read``
                   children cover (the blocking read of a step's result)
    gap_names      what the host was doing in a device idle gap
    op_scopes      HLO instruction -> ``op_name``, from a compiled program's
                   text (the trace's op events carry the instruction only)
    phase_ns       device time of one program run per phase scope
    draft_ms       the draft heads' device time per verify-step run

``tree_attn_roofline`` (``harness.derive``) reads the engine's
``kv_tokens_attended`` counter.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from harness import trace as tr

ENGINE_PREFIX = "engine."
ITERATION = "engine.iteration"
READ = "engine.read"
# phases of the verify step that belong to the draft heads
DRAFT_PHASES = ("draft", "draft_prefix")

Span = Tuple[str, int, int, dict]


def engine_spans(planes) -> List[Span]:
    """(name, start_ns, end_ns, arguments) of every ``engine.*`` event of
    the host planes.  ``planes`` as for ``trace.reduce_planes``; an event's
    ``stats`` are (key, value) pairs."""
    return [(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
            for p in planes if not p.name.startswith(tr.DEVICE_PREFIX)
            for line in p.lines for ev in line.events
            if ev.name.startswith(ENGINE_PREFIX)]


def _covered(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi] that ``intervals`` cover."""
    inside = tr.union([(max(s, lo), min(e, hi)) for s, e in intervals
                       if e > lo and s < hi])
    return sum(e - s for s, e in inside)


def host_loop_ns(spans: List[Span], lo: int, hi: int) -> List[int]:
    """For each ``engine.iteration`` inside [lo, hi], its duration less the
    part its ``engine.read`` spans cover: host work per loop pass."""
    reads = [(s, e) for n, s, e, _ in spans if n == READ]
    return [(e - s) - _covered(reads, s, e) for n, s, e, _ in spans
            if n == ITERATION and lo <= s and e <= hi]


def gap_names(spans: List[Span], s: int, e: int) -> Optional[str]:
    """The engine spans that overlap [s, e], the loop pass itself left out
    unless nothing inside it does; None where no engine span does."""
    names = {n for n, hs, he, _ in spans if hs < e and he > s}
    inner = names - {ITERATION}
    return "+".join(sorted(inner or names)) or None


_METADATA = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*metadata=\{op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    program's text (``compiled.as_text()``) that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _METADATA.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def phase(op_name: str) -> str:
    """``jit(verify_step)/draft/top_k`` -> ``draft``; "" outside a scope."""
    parts = op_name.split("/")
    p = parts[1].rstrip(":") if len(parts) > 2 else ""
    return "" if p.startswith("jit(") else p


def phase_ns(module: tr.Module, scopes: Dict[str, str]) -> Dict[str, int]:
    """Device time of one program run per phase, the container ops left
    out as ``trace.top_ops`` does (their events span the ops they run)."""
    out: Dict[str, int] = {}
    for name, s, e in module.ops:
        if tr.op_base(name) in tr.CONTAINER_OPS:
            continue
        instr = name.split(" = ", 1)[0].lstrip("%")
        p = phase(scopes.get(instr, ""))
        out[p] = out.get(p, 0) + (e - s)
    return out


def draft_ms(runs: List[tr.Module], scopes: Dict[str, str]
             ) -> Optional[float]:
    """Mean device time per verify-step run of the ops scoped ``draft`` or
    ``draft_prefix``."""
    if not runs:
        return None
    ns = sum(phase_ns(m, scopes).get(p, 0) for m in runs
             for p in DRAFT_PHASES)
    return 1e-6 * ns / len(runs)
