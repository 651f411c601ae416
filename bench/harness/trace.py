"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Reads with ``jax.profiler.ProfileData`` only.  A device plane is one whose
name starts with ``/device:TPU:``; on it, the line ``XLA Ops`` holds one
event per executed operation and ``XLA Modules`` one per program run.
Everything is in nanoseconds on the trace's clock.

    busy      union of the operation intervals of a device
    modules   program runs, each with the operations inside it, so that a
              program can be told by an operation it holds (the tree
              attention kernel marks the verify step)
    idle gaps the holes in the busy union inside the traced window,
              labelled by the harness's own host spans that overlap them
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Module:
    name: str
    start: int
    end: int
    ops: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class DeviceTrace:
    busy_ns: int
    first_ns: int
    last_ns: int
    modules: List[Module]
    op_time: Dict[str, int]
    busy_intervals: List[Tuple[int, int]]
    host_spans: List[Tuple[str, int, int]]


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.end_ns)) for ev in line.events]


def reduce_planes(planes) -> List[DeviceTrace]:
    """One ``DeviceTrace`` per device plane.  ``planes`` is anything with
    ``.name`` and ``.lines`` (each with ``.name`` and ``.events``)."""
    planes = [(p.name, [(line.name, _events(line)) for line in p.lines])
              for p in planes]
    host_spans = []
    for name, lines in planes:
        if name.startswith(DEVICE_PREFIX):
            continue
        for _, events in lines:
            host_spans += [e for e in events
                           if e[0].startswith(HOST_SPAN_PREFIX)]
    out = []
    for name, lines in planes:
        if not name.startswith(DEVICE_PREFIX):
            continue
        lines = dict(lines)
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])
        if not ops and not mods:
            continue
        busy_iv = union([(s, e) for _, s, e in (ops or mods)])
        op_time: Dict[str, int] = {}
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0) + (e - s)
        modules = [Module(n, s, e) for n, s, e in sorted(mods,
                                                        key=lambda x: x[1])]
        # attach each op to the module run that contains it
        j = 0
        for n, s, e in sorted(ops, key=lambda x: x[1]):
            while j < len(modules) and modules[j].end < s:
                j += 1
            if j < len(modules) and modules[j].start <= s:
                modules[j].ops.append((n, s, e))
        all_iv = [iv for iv in busy_iv]
        out.append(DeviceTrace(
            busy_ns=sum(e - s for s, e in busy_iv),
            first_ns=all_iv[0][0] if all_iv else 0,
            last_ns=all_iv[-1][1] if all_iv else 0,
            modules=modules, op_time=op_time, busy_intervals=busy_iv,
            host_spans=host_spans))
    return out


def load(log_dir: str) -> List[DeviceTrace]:
    from jax.profiler import ProfileData
    path = find_xplane(log_dir)
    if path is None:
        return []
    return reduce_planes(ProfileData.from_file(path).planes)


def has_op(module: Module, pattern: str) -> bool:
    rx = re.compile(pattern)
    return any(rx.search(n) for n, _, _ in module.ops)


def op_events(module: Module, pattern: str) -> List[Tuple[str, int, int]]:
    rx = re.compile(pattern)
    return [o for o in module.ops if rx.search(o[0])]


def idle_gaps(dt: DeviceTrace, lo: int, hi: int, top: int = 10
              ) -> List[Tuple[str, int]]:
    """The ``top`` longest holes in the busy union within [lo, hi], each
    named by the harness host spans that overlap it, else by what the
    host was running without a span of ours: the engine's serve loop."""
    gaps = []
    prev = lo
    for s, e in dt.busy_intervals + [(hi, hi)]:
        s, e = min(max(s, lo), hi), min(e, hi)
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        names = sorted({n for n, hs, he in dt.host_spans
                        if hs < e and he > s and n != "bench.traced_window"})
        out.append(("+".join(names) if names
                    else "engine loop (no span)", e - s))
    return out


# ops whose events span the ops they run (their time is not their own)
CONTAINER_OPS = ("while", "conditional", "call")


def op_base(name: str) -> str:
    """``%sort.2 = f32[...] sort(...)`` -> ``sort``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.clone)+$", "", head)


def top_ops(op_time: Dict[str, int], top: int = 10
            ) -> List[Tuple[str, int]]:
    """Device time per operation kind, most first, without the container
    ops (a ``while`` event spans the layer scan's body)."""
    agg: Dict[str, int] = {}
    for n, v in op_time.items():
        b = op_base(n)
        if b not in CONTAINER_OPS:
            agg[b] = agg.get(b, 0) + v
    return sorted(agg.items(), key=lambda kv: -kv[1])[:top]
