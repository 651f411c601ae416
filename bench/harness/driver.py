"""Drives the engine through one measured window.

The window is one ``serve`` call whose ``source`` is a generator run on
the engine's feeder thread.  It yields each request when it falls due.
The harness stamps each request's due time itself, so a request that
waits because the source is held back still counts its wait.  At the window's close the generator takes the
window's counts and raises ``WindowClosed``, which the engine relays out
of ``serve``: nothing that happens after the close is counted.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from harness import traffic

WINDOW_SPAN = "bench.traced_window"
HAND_OVER_SPAN = "bench.request_arrives"


class WindowClosed(Exception):
    """Raised by the source at the window's close to end ``serve``."""


@dataclass(eq=False)
class Record:
    req: object                 # the engine's Request
    index: int
    due: float                  # wall-clock time the request fell due
    yielded: float              # when the source handed it over


@dataclass
class Window:
    t0: float = 0.0
    t_close: float = 0.0
    records: List[Record] = field(default_factory=list)
    stats_open: dict = field(default_factory=dict)
    stats_close: dict = field(default_factory=dict)
    tokens_at_close: int = 0
    done_at_close: int = 0
    # index -> (tokens, time of the last token, time of the first token)
    at_close: dict = field(default_factory=dict)
    trace: dict = field(default_factory=dict)


STAT_KEYS = ("steps", "tokens", "active_slot_steps", "capacity_slot_steps",
             "preemptions", "prefill_chunks", "prefill_tokens",
             "kv_tokens_attended")


def stats_snapshot(stats) -> dict:
    """The engine's counters of ``STAT_KEYS``; one the engine does not
    keep is left out (absent, never 0), and its readings give None."""
    return {k: getattr(stats, k) for k in STAT_KEYS if hasattr(stats, k)}


class Source:
    """The window's request source for one cell."""

    def __init__(self, mix: dict, seconds: float,
                 make_request: Callable, engine_stats,
                 trace_hooks: Optional[tuple] = None):
        self.mix, self.seconds = mix, seconds
        self.make_request = make_request
        self.engine_stats = engine_stats
        self.win = Window()
        self.trace_hooks = trace_hooks
        self._trace_thread = None

    # -- window bookkeeping --------------------------------------------

    def _open(self):
        self.win.t0 = time.time()
        self.win.stats_open = stats_snapshot(self.engine_stats)
        if self.trace_hooks is not None:
            self._trace_thread = threading.Thread(
                target=self._trace_window, name="bench-trace", daemon=True)
            self._trace_thread.start()

    def _trace_window(self):
        """Trace the sub-window in the middle of the measured window."""
        start, stop = self.trace_hooks
        trace_s = float(self.mix["trace_s"])
        begin = self.win.t0 + max((self.seconds - trace_s) / 2, 0.0)
        time.sleep(max(begin - time.time(), 0.0))
        tr = self.win.trace
        tr["stats_open"] = stats_snapshot(self.engine_stats)
        tr["t_open"] = time.time()
        start()
        with TraceAnnotation(WINDOW_SPAN):
            time.sleep(max(begin + trace_s - time.time(), 0.0))
        tr["t_close"] = time.time()
        tr["stats_close"] = stats_snapshot(self.engine_stats)
        stop()

    def _close(self):
        end = self.win.t0 + self.seconds
        time.sleep(max(end - time.time(), 0.0))
        if self._trace_thread is not None:
            self._trace_thread.join()
        w = self.win
        w.t_close = time.time()
        w.stats_close = stats_snapshot(self.engine_stats)
        w.at_close = {r.index: (len(r.req.output), r.req.t_last_emit,
                                r.req.t_first_token) for r in w.records}
        w.tokens_at_close = sum(n for n, _, _ in w.at_close.values())
        w.done_at_close = sum(bool(r.req.done) for r in w.records)
        raise WindowClosed()

    def _hand_over(self, index: int, due: float, prompt_len: int,
                   output_len: int):
        with TraceAnnotation(HAND_OVER_SPAN):
            req = self.make_request(index, prompt_len, output_len)
            self.win.records.append(Record(req, index, due, time.time()))
        return req

    # -- the generator -------------------------------------------------

    def __iter__(self):
        arrivals = traffic.open_schedule(self.mix, self.seconds)
        self._open()
        for i, a in enumerate(arrivals):
            due = self.win.t0 + a.due_s
            time.sleep(max(due - time.time(), 0.0))
            yield self._hand_over(i, due, a.prompt_len, a.output_len)
        self._close()


def run_window(eng, source: Source, max_batch: int) -> Window:
    try:
        eng.serve(source=iter(source), max_batch=max_batch, warmup=False)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("serve returned before the window closed")
    return source.win
