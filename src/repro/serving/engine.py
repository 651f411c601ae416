"""Speculative serving engines (paper §6.2: batched inference).

Three schedulers over the same jitted decode step:

``SpeculativeEngine`` — continuous batching over a dense cache.  A fixed
pool of ``max_batch`` slots and a FIFO request queue.  A request joins the
pool the moment a slot is free (per-slot prefill via ``join_slot``:
variable prompt lengths are right-padded to a bucket and length-masked),
decodes with its own per-slot ``cache_len``/budget/EOS, and its slot is
freed and refilled the moment it finishes.  Finished rows are masked out
of the step with ``active`` (the static-shape forward still spans them,
but they emit PAD, advance no cache, and are excluded from
throughput/acceptance statistics) — the FLOP win comes from refilling
freed slots with queued work instead of draining.  The jitted step
signature depends only on ``(max_batch, tree)`` — never on queue
occupancy — so the engine compiles exactly one step (plus one prefill per
prompt-length bucket).

The serve loop is **asynchronous and double-buffered** by default
(DESIGN.md §7): step ``k+1`` is dispatched before step ``k``'s emissions
are read back, so host-side harvest/join/allocator work overlaps device
compute.  All device→host reads (emissions, the first token a join
samples) run one step behind the dispatch frontier; ``inflight=1``
restores the fully synchronous loop.  The overlap reorders host
bookkeeping only — never device math — so greedy outputs are byte-exact
across ``inflight`` settings (a tested invariant).  Requests arrive
through a live queue: ``submit()`` enqueues at any time (including
mid-serve, from a ``source`` callable/generator handed to ``serve`` —
pulled by a background feeder thread through a bounded handoff queue,
so a slow source can never stall the dispatch path) and ``drain()``
serves whatever has been submitted.

**Chunked prefill** (DESIGN.md §8, ``prefill_chunk > 0``): instead of
one monolithic ``join_slot`` stalling every active slot for a long
prompt's whole prefill, prompts stream in fixed-size chunks the
scheduler interleaves with decode steps — at most ``prefill_budget``
prompt tokens co-scheduled per step.  Slots pass through joining →
prefilling → active; only the final chunk samples the request's first
token and activates the slot.  Chunking is pure scheduling: greedy
output is byte-identical to the unchunked engine and to serial
``generate()`` at any chunk size (tested for dense, paged, and
recurrent archs).  Caveat for MoE archs: expert-capacity overflow is
resolved per forward call, so a chunk boundary can change which tokens
drop once routing exceeds capacity — byte-parity there holds only
while routing stays under capacity (DESIGN.md §8).

``PagedSpeculativeEngine`` — the same scheduler over a paged KV cache
(``serving/paged.py``, DESIGN.md §6).  Attention caches live in a global
block pool that may be smaller than ``max_batch × max_len``
(oversubscription); per-slot block tables are grown on demand by a
host-side free-list allocator.  Exhaustion is never a crash: requests
that don't fit wait in the queue (admission control), and when an active
slot can no longer grow, the most-recently-joined slot is preempted —
its blocks are freed and the request is requeued at the front, to be
re-prefilled later from prompt + tokens-so-far (byte-exact under greedy
decoding).

``BucketedEngine`` — the legacy static scheduler kept as the baseline:
requests are grouped by exact prompt length, each batch runs to
completion, and a batch's slowest row drains while the others idle.
Benchmarks (paper Figs. 2/3) report both so the slot-utilization win is
measurable.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Iterable, List, NamedTuple, Optional,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models.model import paged_kernel_covers
from repro.core.speculative import (autoregressive_step, init_decode_state,
                                    init_pool_state, join_slot,
                                    join_slot_chunk, max_emitted_per_step,
                                    spec_decode_step)
from repro.serving.paged import (NULL_BLOCK, BlockAllocator, init_paged_state,
                                 paged_autoregressive_step, paged_join_slot,
                                 paged_join_slot_chunk, paged_spec_decode_step)

# feeder-thread end-of-stream marker (see SpeculativeEngine._feed_source)
_SOURCE_DONE = object()

# program names of the (non-final, final) prefill chunk
_CHUNK_NAMES = {False: "prefill_chunk", True: "prefill_chunk_final"}


def _snapshot(host_array: np.ndarray):
    """Device operand from a MUTABLE host array, copy-guaranteed.

    ``jnp.asarray`` of an aligned numpy array can be ZERO-COPY on the CPU
    backend — the device buffer then aliases the live numpy memory, and a
    host mutation (harvest clearing an ``active`` bit, the allocator
    rewriting a block-table row) races with any still-executing dispatch
    that took the "snapshot".  Whether a given array aliases depends on
    its heap alignment, which is why the resulting corruption was a
    per-process coin flip.  Copying on the host first guarantees the
    device operand is frozen at dispatch time, which is what the async
    loop's correctness argument (DESIGN.md §7 "snapshotted per
    dispatch") requires."""
    return jnp.asarray(host_array.copy())


def _named(name: str, fn):
    """``fn`` under ``name``: ``jax.jit`` calls its XLA module
    ``jit_<name>``, the name a profiler trace shows for each run."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclass
class Request:
    """One generation request.

    ``prompt`` is the token context; the engine appends every generated
    token (including the one sampled at prefill) to ``output`` and sets
    ``done`` when the budget is exhausted or ``eos_token`` is produced.
    ``output`` survives preemption: a preempted request resumes by
    re-prefilling ``prompt + output``.  ``rid`` is assigned by the engine
    when the request is queued; the profiler spans of its joins, chunks
    and preemptions carry it.
    """

    prompt: np.ndarray
    max_new_tokens: int = 64
    eos_token: Optional[int] = None
    output: List[int] = field(default_factory=list)
    done: bool = False
    rid: Optional[int] = None
    # serving timeline (wall-clock seconds, filled in by the engine)
    t_enqueue: Optional[float] = None
    t_join: Optional[float] = None
    t_first_token: Optional[float] = None
    t_last_emit: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def latency_s(self) -> Optional[float]:
        """Queue-to-finish latency (None until the request completes)."""
        if self.t_done is None or self.t_enqueue is None:
            return None
        return self.t_done - self.t_enqueue

    @property
    def ttft_s(self) -> Optional[float]:
        """Queue-to-first-token latency (None until the first token)."""
        if self.t_first_token is None or self.t_enqueue is None:
            return None
        return self.t_first_token - self.t_enqueue


@dataclass
class EngineStats:
    """Accumulated serving counters (one instance per engine, across every
    ``serve`` call).

    Fields
    ------
    steps            jitted decode steps executed (prefills not counted)
    tokens           tokens delivered to requests post-prefill (clamped at
                     each request's budget; PAD / dead-slot emissions and
                     the prefill token are excluded)
    wall_s           wall-clock seconds inside the serving loop (warmup
                     compiles excluded)
    warmup_s         wall-clock seconds of that warmup: compiling, and
                     running once, the step and every prefill program the
                     queued requests need
    host_stall_s     seconds the host spent working while NO step was in
                     flight — i.e. time the host-side harvest/join/
                     allocator bookkeeping STARVED the device pipeline.
                     This is the serialization the async loop exists to
                     remove: with ``inflight>=2`` host work runs behind a
                     dispatched step (stall ~0); the synchronous loop
                     (``inflight=1``) pays it between every read and the
                     next dispatch
    read_wait_s      seconds blocked inside device→host reads (step
                     emissions, deferred join tokens) — device-bound
                     time, reported separately so host-caused stall
                     isn't conflated with waiting on compute
    steps_in_flight  high-water mark of dispatched-but-unharvested steps
                     (1 = synchronous loop, 2 = double-buffered)
    kv_tokens_attended
                     over harvested steps, the cached context each live
                     row's verify attended, summed: the row's committed
                     length (prompt + output - 1) when the step ran, from
                     the host's own bookkeeping (no device read)
    active_slot_steps / capacity_slot_steps
                     slot-occupancy accounting: capacity counts
                     ``max_batch`` slots per step, active counts rows that
                     held a live (not-yet-finished) request
    request_latency_s per-request queue-to-finish latencies

    Paged-cache accounting (all zero for dense engines):

    block_size / num_blocks   pool geometry (tokens per block, physical
                              blocks incl. the reserved NULL block)
    pool_tokens               usable pool capacity in cache positions
    dense_equiv_tokens        what a dense cache would reserve for the
                              same serve call (``max_batch × max_len``)
    peak_blocks_in_use        high-water mark of allocated blocks
    preemptions               slots evicted to the queue on pool
                              exhaustion (re-prefilled later)
    step_transient_tokens     cache positions each jitted step materializes
                              as a transient on top of the persistent
                              reservation: 0 for dense (in-place updates);
                              ``max_batch × T`` scratch writes for the
                              native paged kernel; ``max_batch × max_len``
                              when any layer takes the per-LAYER gather
                              fallback (sliding-window groups, MLA) — one
                              layer's view at a time — and for the shim
                              oracle, whose view additionally spans all L
                              layers at once (same positions, L× bytes)
    """

    steps: int = 0
    tokens: int = 0
    wall_s: float = 0.0
    warmup_s: float = 0.0
    host_stall_s: float = 0.0
    read_wait_s: float = 0.0
    steps_in_flight: int = 0
    kv_tokens_attended: int = 0
    active_slot_steps: int = 0
    capacity_slot_steps: int = 0
    request_latency_s: List[float] = field(default_factory=list)
    # responsiveness: queue-to-first-token per request, and per-token
    # inter-token gaps (a harvest delivering n tokens after gap g
    # contributes n samples of g/n — burst emissions don't hide stalls).
    # p99_itl_s is the tail the chunked-prefill scheduler exists to fix:
    # a monolithic long-prompt join stalls EVERY active slot for one
    # prefill, which lands here as a fleet-wide gap spike (DESIGN.md §8)
    ttft_s: List[float] = field(default_factory=list)
    itl_s: List[float] = field(default_factory=list)
    # chunked-prefill accounting (zero when prefill_chunk is off)
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    # paged-KV accounting (zero when the cache is dense)
    block_size: int = 0
    num_blocks: int = 0
    pool_tokens: int = 0
    dense_equiv_tokens: int = 0
    peak_blocks_in_use: int = 0
    preemptions: int = 0
    step_transient_tokens: int = 0

    @property
    def tokens_per_step(self) -> float:
        return self.tokens / max(self.steps, 1)

    @property
    def host_stall_frac(self) -> float:
        """Fraction of serving wall-clock during which host bookkeeping
        starved the device pipeline (no step in flight)."""
        return self.host_stall_s / max(self.wall_s, 1e-9)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / max(self.wall_s, 1e-9)

    @property
    def slot_utilization(self) -> float:
        return self.active_slot_steps / max(self.capacity_slot_steps, 1)

    @property
    def mean_latency_s(self) -> float:
        lat = self.request_latency_s
        return float(np.mean(lat)) if lat else 0.0

    @property
    def p99_latency_s(self) -> float:
        lat = self.request_latency_s
        return float(np.percentile(lat, 99)) if lat else 0.0

    @property
    def mean_ttft_s(self) -> float:
        return float(np.mean(self.ttft_s)) if self.ttft_s else 0.0

    @property
    def p99_ttft_s(self) -> float:
        return float(np.percentile(self.ttft_s, 99)) if self.ttft_s else 0.0

    @property
    def mean_itl_s(self) -> float:
        return float(np.mean(self.itl_s)) if self.itl_s else 0.0

    @property
    def p99_itl_s(self) -> float:
        """p99 inter-token latency across every served token — the
        long-prompt head-of-line metric (see the field comment)."""
        return float(np.percentile(self.itl_s, 99)) if self.itl_s else 0.0

    @property
    def peak_pool_tokens(self) -> int:
        """High-water mark of cache positions actually backed by blocks."""
        return self.peak_blocks_in_use * self.block_size

    @property
    def kv_pool_frac(self) -> float:
        """Pool reservation as a fraction of the dense-equivalent HBM
        (< 1.0 means the pool oversubscribes ``max_batch × max_len``)."""
        if not self.dense_equiv_tokens:
            return 1.0
        return self.pool_tokens / self.dense_equiv_tokens


class _StepRecord(NamedTuple):
    """One dispatched-but-unharvested decode step (DESIGN.md §7).

    Everything the harvest needs is snapshotted at dispatch time: the
    ``active`` mask and slot→request assignment the step ran with (host
    state moves on while the step is in flight), plus the joins issued
    just before it — each carrying the joined state's ``last_token``
    device array so the first sampled token can be read one step behind,
    without flushing the pipeline at join time.  Only the emission
    arrays are retained — holding the whole ``StepResult`` would keep
    the step's full cache pytree alive one extra step for nothing.
    """

    emitted: Any                    # (B, D+1) device future
    n_emitted: Any                  # (B,) device future
    active: np.ndarray              # (B,) bool mask the step was run with
    slots: List[Optional["Request"]]  # slot→request snapshot at dispatch
    joins: List[tuple]              # [(slot, Request, last_token devarray)]
    max_batch: int


@dataclass
class _PrefillJob:
    """Host-side progress of one chunked prefill (slot state 'prefilling',
    DESIGN.md §8).  ``ctx`` is the request's context (prompt + any
    resumed output) right-padded to a chunk multiple; ``off`` is the
    prefill cursor — tokens already dispatched to the device.  The device
    mirror of ``off`` is ``cache_len[slot]``, which the chunk updates so
    concurrent decode steps scribble their dead-row scratch *ahead* of
    the cursor (where the next chunk overwrites it), never behind."""

    request: "Request"
    ctx: np.ndarray
    real_len: int
    off: int = 0

    @property
    def done(self) -> bool:
        return self.off >= len(self.ctx)


# A live request source for ``serve``: an iterable (pulled lazily as slot
# capacity frees up; exhaustion ends the stream) or a zero-arg callable
# polled by the feeder thread (returns newly arrived requests, an empty
# iterable for "nothing yet, keep serving", or None for "no more ever").
RequestSource = Union[Iterable["Request"], Callable[[], Any]]


class _EngineBase:
    """Shared jitted-step plumbing for all schedulers."""

    def __init__(self, params, draft_params, cfg: ModelConfig, tree, *,
                 max_len: int = 2048, criterion: str = "greedy",
                 use_speculative: bool = True, temperature: float = 0.7,
                 epsilon: float = 0.15, seed: int = 0):
        self.params = params
        self.draft_params = draft_params
        self.cfg = cfg
        self.tree = tree
        self.max_len = max_len
        self.criterion = criterion
        self.use_speculative = use_speculative
        self.temperature = temperature
        self.epsilon = epsilon
        self.rng = jax.random.PRNGKey(seed)
        if use_speculative:
            self._step = jax.jit(_named(
                "verify_step", lambda p, dp, st, act: spec_decode_step(
                    p, dp, cfg, tree, st, criterion=criterion,
                    temperature=temperature, epsilon=epsilon, active=act)))
        else:
            self._step = jax.jit(_named(
                "decode_step", lambda p, _dp, st, act: autoregressive_step(
                    p, cfg, st, greedy=(criterion == "greedy"),
                    temperature=temperature, active=act)))
        self.stats = EngineStats()

    def _run_step(self, state, active=None):
        return self._step(self.params, self.draft_params, state, active)

    def _note_emission(self, r: "Request", appended: int) -> None:
        """Inter-token-latency samples for one emission batch: a gap of g
        seconds delivering n tokens contributes n samples of g/n, so
        speculative bursts don't mask scheduler stalls between them."""
        now = time.time()
        if r.t_last_emit is not None:
            gap = (now - r.t_last_emit) / appended
            self.stats.itl_s.extend([gap] * appended)
        r.t_last_emit = now

    def _note_first_token(self, r: "Request") -> None:
        now = time.time()
        if r.t_first_token is None:
            r.t_first_token = now
            if r.t_enqueue is not None:
                self.stats.ttft_s.append(now - r.t_enqueue)
        r.t_last_emit = now


class SpeculativeEngine(_EngineBase):
    """Continuous-batching speculative engine (the default serving path).

    Public API
    ----------
    ``submit(request)`` enqueues (FIFO) at any time — before, between, or
    during ``serve`` calls.  ``serve(requests=(), *, source=None,
    max_batch=8, warmup=True) -> EngineStats`` runs the loop until the
    queue, the optional live ``source`` (see ``RequestSource``), and all
    in-flight steps drain; ``drain()`` is ``serve`` over what has been
    submitted.  The lifecycle per request: **enqueue** -> **join** the
    moment a slot frees (bucketed prefill; its first sampled token is
    read back one step later) -> **harvest** one step behind dispatch
    (accepted + bonus tokens appended to ``Request.output``, clamped at
    ``max_new_tokens``, cut at ``eos_token``) -> **finish** (slot freed
    and refilled from the queue).  ``serve`` may be called repeatedly;
    ``stats`` accumulates across calls.

    Async pipeline (DESIGN.md §7): with ``inflight=2`` (the default) the
    loop dispatches step ``k+1`` before reading step ``k``'s emissions,
    so joins, admissions, growth/preemption, and the Python harvest all
    run while the device is busy.  Host state (``Request.output``, the
    paged allocator) is therefore one step stale at dispatch time; every
    capacity decision budgets for that staleness
    (``_stale_allowance``), and a request discovered finished at harvest
    may ride through one already-dispatched step as a masked "zombie"
    row whose emissions are discarded.  Device math never reorders, so
    greedy outputs are byte-exact for any ``inflight`` (tested).
    ``inflight=1`` is the synchronous loop.

    Active-mask semantics: the jitted step always spans ``max_batch``
    rows.  Rows whose slot is empty or whose request finished ride along
    with ``active=False`` — they emit PAD, advance no ``cache_len``, and
    keep token/hidden/recurrent state bit-frozen — so occupancy never
    retraces the step (one compile per ``(max_batch, tree)``).

    ``prefill_bucket`` rounds prompt lengths up before the per-slot
    prefill so the number of compiled join functions is bounded (one per
    bucket) — for every arch: recurrent state groups (mamba/rwkv) ride
    the length-masked scan, which carries state past right-pad tokens
    unchanged (models/ssm.py, DESIGN.md §8).  With ``prefill_chunk`` the
    bucket is the chunk instead, and prompts prefill incrementally
    through the joining → prefilling → active slot lifecycle (§8).

    Subclass hooks (``_admit`` / ``_before_step`` / ``_release`` /
    ``_advance`` / ``_post_serve``) are no-ops here; the paged engine
    overrides them for block accounting — the serve loop itself is
    scheduler-agnostic.
    """

    def __init__(self, params, draft_params, cfg: ModelConfig, tree, *,
                 prefill_bucket: int = 32, prefill_chunk: int = 0,
                 prefill_budget: Optional[int] = None, inflight: int = 2,
                 **kw):
        super().__init__(params, draft_params, cfg, tree, **kw)
        # the length-masked recurrent scan (models/ssm.py) carries state
        # past right-pads unchanged, so bucketed padding is legal for
        # mamba2/rwkv6 too — no more one-compile-per-prompt-length
        self.prefill_bucket = max(int(prefill_bucket), 1)
        # chunked prefill (DESIGN.md §8): 0 = monolithic join (legacy).
        # Recurrent archs round the chunk up to the inner scan chunk so a
        # chunk boundary is always an inner-chunk boundary — the scan's
        # state grouping (hence the bits) then matches the monolithic run.
        prefill_chunk = int(prefill_chunk or 0)
        if prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0: {prefill_chunk}")
        if prefill_chunk and cfg.block_kind in ("mamba2", "rwkv6"):
            inner = cfg.ssm.chunk_size if cfg.ssm else 64
            prefill_chunk = -(-prefill_chunk // inner) * inner
        self.prefill_chunk = prefill_chunk
        if prefill_chunk:
            self.prefill_budget = int(prefill_budget or prefill_chunk)
            if self.prefill_budget < prefill_chunk:
                raise ValueError(
                    f"prefill_budget {self.prefill_budget} < prefill_chunk "
                    f"{prefill_chunk}: the scheduler could never dispatch "
                    f"a chunk")
        else:
            self.prefill_budget = 0
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1: {inflight}")
        self.inflight = int(inflight)
        self._rids = itertools.count()
        self._queue: deque = deque()
        self._inflight: deque = deque()
        self._live_joins: dict = {}          # slot -> (Request, last_token)
        self._prefills: dict = {}            # slot -> _PrefillJob
        # does the chunk attention view grow with the prefill cursor?
        # Pure-recurrent archs without a Hydra++ prefix cache carry no
        # sequence-axis cache at all — one full-extent trace suffices
        from repro.models.model import group_program
        self._view_grows = (
            any(k.startswith("attn") or k == "hybrid"
                for k, _ in group_program(cfg))
            or (draft_params is not None and "prefix" in draft_params))
        greedy = self.criterion == "greedy"
        # jit retraces per padded prompt shape, i.e. one compile per bucket
        self._join_fn = jax.jit(_named(
            "join", lambda p, dp, st, prompt, rl, slot: join_slot(
                p, dp, cfg, st, prompt, rl, slot, greedy=greedy)))
        # chunked prefill compiles one (non-final, final) executable pair
        # per VIEW EXTENT (power-of-two ladder, <= log2(max_len) of them)
        # — independent of how many distinct prompt lengths are served
        self._chunk_fns = {
            fin: jax.jit(_named(
                _CHUNK_NAMES[fin],
                lambda p, dp, st, ch, start, rl, slot, view, _f=fin:
                join_slot_chunk(p, dp, cfg, st, ch, start, rl, slot,
                                final=_f, view_len=view, greedy=greedy)),
                static_argnums=7)
            for fin in (False, True)} if prefill_chunk else {}

    # -- prefill-on-join -----------------------------------------------------

    def _pad_len(self, n: int) -> int:
        # chunked prefill pads the context to a chunk multiple instead of
        # a bucket multiple (every chunk is exactly prefill_chunk wide)
        b = self.prefill_chunk or self.prefill_bucket
        return max(-(-n // b) * b, b)

    @property
    def _scratch(self) -> int:
        """Cache positions one verify step writes past ``cache_len``."""
        return self.tree.size if self.use_speculative else 1

    @property
    def _max_emit(self) -> int:
        """Most tokens one step can commit to a row (accepted + bonus)."""
        return max_emitted_per_step(self.tree,
                                    speculative=self.use_speculative)

    @property
    def _stale_allowance(self) -> int:
        """Cache positions a row can advance past the host's knowledge.

        At dispatch time up to ``inflight - 1`` steps are unharvested,
        each committing at most ``_max_emit`` tokens, so every capacity
        decision (admission, growth, the up-front reject) budgets this
        many extra positions.  Zero for the synchronous loop — the
        formulas below then reduce exactly to the pre-async ones.
        """
        return (self.inflight - 1) * self._max_emit

    def _context(self, r: Request) -> np.ndarray:
        """Prefill context: the prompt, plus tokens already generated when
        the request is resuming after a preemption."""
        ctx = np.asarray(r.prompt, np.int32)
        if r.output:
            ctx = np.concatenate([ctx, np.asarray(r.output, np.int32)])
        return ctx

    def _padded_context(self, r: Request):
        """(bucket-padded prompt array, real length) for a join/rejoin."""
        ctx = self._context(r)
        n = len(ctx)
        padded = np.zeros(self._pad_len(n), np.int32)
        padded[:n] = ctx
        return padded, n

    def _warm_buckets(self, requests: List[Request]) -> set:
        """Padded prompt lengths to precompile joins for.  Empty under
        chunked prefill — the two chunk executables cover every prompt
        length (including post-preemption resumes), so there are no
        per-bucket compiles to warm."""
        if self.prefill_chunk:
            return set()
        return {self._pad_len(len(r.prompt)) for r in requests}

    def _check_capacity(self, r: Request) -> None:
        # the stale allowance covers the one zombie step a finished
        # request may ride through before the harvest discovers it
        need = (self._pad_len(len(r.prompt)) + r.max_new_tokens
                + self._scratch + self._stale_allowance)
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache slots (padded prompt "
                f"{self._pad_len(len(r.prompt))} + budget {r.max_new_tokens} "
                f"+ {self._scratch} verify scratch + {self._stale_allowance} "
                f"async staleness) but max_len={self.max_len}")

    def _join(self, state, slot: int, r: Request):
        padded, n = self._padded_context(r)
        return self._join_fn(self.params, self.draft_params, state,
                             jnp.asarray(padded), jnp.int32(n),
                             jnp.int32(slot))

    def _warm_join(self, state, P: int):
        return self._join_fn(self.params, self.draft_params, state,
                             jnp.zeros(P, jnp.int32), jnp.int32(1),
                             jnp.int32(0))

    # -- chunked prefill (DESIGN.md §8) --------------------------------------

    def _chunk_view_len(self, end: int) -> int:
        """Static attention-view extent for a chunk whose write region
        ends at ``end``: the next power of two >= max(end, 64), clamped
        to the row capacity.  Masked tails are exact no-ops, so the
        extent never changes bits — only how much of the cache the chunk
        sweeps (and how many traces exist: one per extent)."""
        cap = self.max_len
        if not self._view_grows:
            return cap
        v = 64
        while v < min(end, cap):
            v *= 2
        return min(v, cap)

    def _chunk_views(self, requests: List[Request]) -> set:
        """View extents the queued requests' chunks will need (for
        warmup; a live-submitted longer prompt pays its own compile,
        like a new bucket used to)."""
        views = set()
        if not self.prefill_chunk:
            return views
        for r in requests:
            n = self._pad_len(len(r.prompt))
            for end in range(self.prefill_chunk, n + 1, self.prefill_chunk):
                views.add(self._chunk_view_len(end))
        return views

    def _dispatch_chunk(self, state, si: int, chunk: np.ndarray, start: int,
                        real_len: int, final: bool):
        """Queue one prefill chunk into the device lane (no host reads)."""
        view = self._chunk_view_len(start + self.prefill_chunk)
        return self._chunk_fns[final](
            self.params, self.draft_params, state, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(real_len), jnp.int32(si), view)

    def _warm_chunk(self, state, final: bool, view: int):
        return self._chunk_fns[final](
            self.params, self.draft_params, state,
            jnp.zeros(self.prefill_chunk, jnp.int32), jnp.int32(0),
            jnp.int32(1), jnp.int32(0), view)

    def _start_prefill(self, si: int, r: Request, slots) -> None:
        """Move a queue head into slot ``si`` in the 'prefilling' state:
        the slot is owned (joins/refills skip it) but inactive (decode
        steps mask it) until its final chunk lands."""
        padded, n = self._padded_context(r)
        self._prefills[si] = _PrefillJob(request=r, ctx=padded, real_len=n)
        slots[si] = r
        r.t_join = time.time()
        self._seq += 1
        self._join_seq[si] = self._seq

    def _pump_prefill(self, si: int, state, active, slots, pending,
                      joins: list, budget: int):
        """Dispatch as many of slot ``si``'s remaining chunks as ``budget``
        allows.  The final chunk activates the slot and registers the
        deferred first-token read exactly like a monolithic join."""
        C = self.prefill_chunk
        while si in self._prefills and budget >= C:
            job = self._prefills[si]
            with TraceAnnotation("engine.alloc"):
                grown = self._grow_prefill(si, job, slots, active, pending)
            if not grown:
                break                      # pool dry even after preemption?
            if si not in self._prefills:
                break                      # _grow_prefill preempted us
            start, end = job.off, job.off + C
            final = end >= len(job.ctx)
            with TraceAnnotation("engine.prefill_chunk", rid=job.request.rid,
                                 start=start, final=final):
                state = self._dispatch_chunk(state, si, job.ctx[start:end],
                                             start, job.real_len, final)
            self._device_fed()
            job.off = end
            budget -= C
            self.stats.prefill_chunks += 1
            self.stats.prefill_tokens += max(
                min(end, job.real_len) - start, 0)
            self._advance_prefill_cursor(si, min(end, job.real_len))
            if final:
                r = job.request
                del self._prefills[si]
                active[si] = True
                self._live_joins[si] = (r, state.last_token)
                joins.append((si, r, state.last_token))
        return state, budget

    def _advance_prefills(self, state, slots, active, pending,
                          joins: list):
        """The chunked-prefill lane of one loop iteration: advance
        in-progress prefills oldest-first, then admit queue heads into
        free slots — dispatching at most ``prefill_budget`` prompt tokens
        in total, so the decode step this iteration co-schedules with
        never waits on more than a bounded slice of prefill work."""
        budget = self.prefill_budget
        for si in sorted(self._prefills, key=lambda s: self._join_seq[s]):
            state, budget = self._pump_prefill(si, state, active, slots,
                                               pending, joins, budget)
        for si in range(len(slots)):
            if budget < self.prefill_chunk or not pending:
                break
            if active[si] or si in self._prefills:
                continue
            if not self._admit_prefill(pending[0]):
                break                      # strict FIFO: head blocks tail
            r = pending.popleft()
            self._start_prefill(si, r, slots)
            state, budget = self._pump_prefill(si, state, active, slots,
                                               pending, joins, budget)
        return state

    # -- scheduler hooks (paged engine overrides; dense cache needs none) ----

    def _admit_prefill(self, r: Request) -> bool:
        """Admission for a chunked join — the paged engine prices only the
        FIRST chunk's blocks (incremental allocation, §8)."""
        return self._admit(r)

    def _grow_prefill(self, si: int, job: _PrefillJob, slots, active,
                      pending) -> bool:
        """Ensure capacity for the next chunk's writes (paged: allocate
        its blocks, preempting on exhaustion).  Dense caches always have
        the full row."""
        return True

    def _advance_prefill_cursor(self, si: int, n: int) -> None:
        """Host mirror of the prefill cursor (paged: ``_slot_len``)."""
        pass

    def _init_pool(self, max_batch: int, rng):
        # record the dense reservation so benchmarks can put dense and
        # paged runs in the same memory column
        self.stats.dense_equiv_tokens = max_batch * self.max_len
        return init_pool_state(self.params, self.draft_params, self.cfg,
                               max_batch, self.max_len, rng)

    def _admit(self, r: Request) -> bool:
        return True

    def _before_step(self, state, slots, active, pending):
        return state

    def _advance(self, slot: int, n_tokens: int) -> None:
        pass

    def _release(self, slot: int) -> None:
        pass

    def _post_serve(self) -> None:
        pass

    # -- live queue ----------------------------------------------------------

    def submit(self, r: Request) -> Request:
        """Enqueue one request (validated up front).  Legal at any time:
        before ``serve``, between calls, or mid-serve from a ``source``
        callback — the loop admits it the moment a slot and (paged)
        blocks are free."""
        self._check_capacity(r)
        if r.t_enqueue is None:
            r.t_enqueue = time.time()
        self._enqueue(r)
        return r

    def _enqueue(self, r: Request) -> None:
        if r.rid is None:
            r.rid = next(self._rids)
        self._queue.append(r)

    def drain(self, *, max_batch: int = 8, warmup: bool = True
              ) -> EngineStats:
        """Serve everything ``submit``-ted so far and return the stats."""
        return self.serve(max_batch=max_batch, warmup=warmup)

    def _feed_source(self, source, q: "queue.Queue",
                     stop: threading.Event) -> None:
        """Background feeder (PR-4 follow-up): pulls from the user's
        ``source`` on its own thread so a slow iterator/callable can never
        starve the device pipeline — the serve loop only ever drains the
        bounded handoff queue, non-blocking.  Callables are polled in a
        tight loop (None => exhausted, empty batch => nothing yet);
        iterators are pulled with the queue's bound as backpressure.  A
        sentinel marks exhaustion; exceptions are carried back to the
        serve loop and re-raised there."""
        try:
            if callable(source):
                while not stop.is_set():
                    batch = source()
                    if batch is None:
                        break
                    got = False
                    for r in batch:
                        got = True
                        if not self._feed_put(q, r, stop):
                            return
                    if not got:
                        # idle poll cadence ~ a decode step, not a spin:
                        # a callable source may do real work (an RPC to
                        # an upstream queue) on every call
                        time.sleep(2e-3)
            else:
                for r in source:
                    if not self._feed_put(q, r, stop):
                        return
        except BaseException as e:             # noqa: BLE001 — relayed
            self._src_err.append(e)
        finally:
            self._feed_put(q, _SOURCE_DONE, stop)

    @staticmethod
    def _feed_put(q: "queue.Queue", item, stop: threading.Event) -> bool:
        """Bounded put that stays responsive to shutdown."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _poll_source(self, pending: deque, max_batch: int) -> None:
        """Drain the feeder thread's handoff queue (never blocks).
        Backpressure: stop draining once ``max_batch`` requests sit
        queued-unjoined — the bounded handoff then throttles the feeder."""
        if self._src_err:
            err = self._src_err[0]
            self._src_done = True
            raise err
        if self._src_done or self._src_q is None:
            return
        while len(pending) < max_batch:
            try:
                item = self._src_q.get_nowait()
            except queue.Empty:
                return
            if item is _SOURCE_DONE:
                self._src_done = True
                return
            self.submit(item)

    # -- serving -------------------------------------------------------------

    def serve(self, requests: Iterable[Request] = (), *,
              source: Optional[RequestSource] = None, max_batch: int = 8,
              warmup: bool = True) -> EngineStats:
        for r in requests:
            self._check_capacity(r)
            self._enqueue(r)           # enqueue-stamped after warmup
        pending = self._queue
        self._src_done = source is None
        self._src_err: List[BaseException] = []
        self._src_q: Optional[queue.Queue] = None
        self._src_stop: Optional[threading.Event] = None
        self._src_thread: Optional[threading.Thread] = None
        if source is not None:
            # feeder thread + bounded handoff: the loop never blocks on
            # (or repeatedly polls) a slow source in the dispatch path
            self._src_q = queue.Queue(maxsize=max(2 * max_batch, 8))
            self._src_stop = threading.Event()
            self._src_thread = threading.Thread(
                target=self._feed_source, args=(source, self._src_q,
                                                self._src_stop),
                name="engine-source-feeder", daemon=True)
            self._src_thread.start()
        self._slots: List[Optional[Request]] = [None] * max_batch
        self._active = np.zeros(max_batch, bool)
        self._inflight = deque()
        self._live_joins = {}
        self._prefills = {}
        self._seq = getattr(self, "_seq", 0)
        self._join_seq = np.zeros(max_batch, np.int64)
        slots, active = self._slots, self._active

        self.rng, sub = jax.random.split(self.rng)
        state = self._init_pool(max_batch, sub)

        t_warm = time.time()
        if warmup:  # compile the step + every join bucket outside the clock
            jax.block_until_ready(self._run_step(
                state, _snapshot(active)).state.cache_len)
            for P in sorted(self._warm_buckets(list(pending))):
                jax.block_until_ready(self._warm_join(state, P).cache_len)
            if self.prefill_chunk:
                views = (self._chunk_views(list(pending))
                         or {self._chunk_view_len(self.prefill_chunk)})
                for view in sorted(views):
                    for fin in (False, True):
                        jax.block_until_ready(
                            self._warm_chunk(state, fin, view).cache_len)
        self.stats.warmup_s += time.time() - t_warm

        # enqueue AFTER warmup so latency measures serving, not XLA
        # compiles (live submit()s carry their own arrival stamp already)
        now = time.time()
        for r in pending:
            if r.t_enqueue is None:
                r.t_enqueue = now

        t0 = time.time()
        # device-starvation accounting: a window opens whenever the
        # in-flight queue drains (device has nothing to chew on) and
        # closes at the next join/step dispatch — its span is host work
        # that serialized with device compute (EngineStats.host_stall_s)
        self._starve_t0: Optional[float] = t0
        try:
            self._serve_loop(pending, max_batch, slots, active, state)
        finally:
            # always reap the feeder thread, even on a deadlock raise or
            # a relayed source exception
            self._stop_feeder()
        self.stats.wall_s += time.time() - t0
        self._post_serve()
        return self.stats

    def _serve_loop(self, pending, max_batch, slots, active, state) -> None:
        # profiler spans (DESIGN.md §7): each iteration is one
        # ``engine.iteration``; its children name the host phases
        for it in itertools.count():
            with StepTraceAnnotation("engine.iteration", step_num=it):
                with TraceAnnotation("engine.poll"):
                    self._poll_source(pending, max_batch)
                if (not pending and not active.any() and not self._inflight
                        and not self._prefills and self._src_done):
                    break

                # harvest-first policy: give up one step of overlap when
                # the read buys better scheduling than the overlap is
                # worth — at a stream's tail (a dispatch could be
                # all-zombie) or when a likely finish would free a
                # slot/blocks for the queue head
                while self._inflight and self._harvest_first(pending):
                    self._harvest(self._inflight.popleft())

                # refill every free slot before the next step (strict
                # FIFO: a head-of-line request the pool can't admit blocks
                # the rest).  Joins/chunks are DISPATCHED into the device
                # lane without flushing the in-flight step; a join's first
                # sampled token is read back at harvest, one step behind.
                joins = []
                with TraceAnnotation("engine.admit"):
                    if self.prefill_chunk:
                        # chunked lane (§8): at most prefill_budget prompt
                        # tokens ride alongside this iteration's decode
                        # step; a slot only activates (and joins the step)
                        # once its final chunk is in
                        state = self._advance_prefills(state, slots, active,
                                                       pending, joins)
                    else:
                        for si in range(max_batch):
                            if active[si] or not pending:
                                continue
                            if not self._admit(pending[0]):
                                break
                            r = pending.popleft()
                            with TraceAnnotation("engine.join", rid=r.rid):
                                state = self._join(state, si, r)
                            self._device_fed()  # prefill queued: not starved
                            r.t_join = time.time()
                            self._live_joins[si] = (r, state.last_token)
                            joins.append((si, r, state.last_token))
                            slots[si] = r
                            active[si] = True
                # paged: grow block tables for the coming step, preempting
                # the most-recently-joined slots back into `pending` on
                # exhaustion
                with TraceAnnotation("engine.alloc"):
                    state = self._before_step(state, slots, active, pending)
                # a join preempted before its step dispatched was
                # force-read and requeued by _preempt; drop it from this
                # step's record
                joins = [(si, r, lt) for si, r, lt in joins
                         if self._live_joins.get(si, (None,))[0] is r]

                if active.any():
                    with TraceAnnotation("engine.dispatch"):
                        res = self._run_step(state, _snapshot(active))
                    self._device_fed()
                    state = res.state
                    self._inflight.append(_StepRecord(
                        res.emitted, res.n_emitted, active.copy(),
                        list(slots), joins, max_batch))
                    self.stats.steps_in_flight = max(
                        self.stats.steps_in_flight, len(self._inflight))
                    # double-buffer: harvest step k only once step k+1 is
                    # in the lane (inflight=1 degenerates to the sync loop)
                    while len(self._inflight) >= self.inflight:
                        self._harvest(self._inflight.popleft())
                elif self._inflight:
                    # nothing dispatchable: drain the pipeline — harvested
                    # finishes free slots/blocks and may unblock admission
                    self._harvest(self._inflight.popleft())
                elif self._prefills:
                    # prefill-only interval (e.g. the pool is all long
                    # prompts): chunks are already queued on the device
                    # each iteration — just keep pumping, nothing to
                    # harvest yet
                    continue
                elif pending:
                    raise RuntimeError(
                        "pool deadlock: no active slots and the queue head "
                        "cannot be admitted — the block pool is too small "
                        "for this request stream")
                else:
                    with TraceAnnotation("engine.idle"):
                        time.sleep(2e-4)   # waiting on a live source
                    self._starve_t0 = time.time()  # no-traffic idle != stall

    def _stop_feeder(self) -> None:
        if self._src_thread is not None:
            self._src_stop.set()
            self._src_thread.join(timeout=2.0)
            # requests the feeder already pulled from the caller's source
            # but the loop never drained (error-path exits: deadlock
            # raise, relayed source exception) must not be lost — park
            # them in the engine queue so a later serve()/drain() still
            # serves them
            while True:
                try:
                    item = self._src_q.get_nowait()
                except queue.Empty:
                    break
                if item is not _SOURCE_DONE:
                    try:
                        self.submit(item)
                    except ValueError:
                        pass   # unservable anyway; don't mask the exit
            self._src_thread = None
            self._src_q = None
            self._src_stop = None

    def _harvest_first(self, pending: deque) -> bool:
        """Should the loop read an in-flight step BEFORE dispatching?

        Run-ahead has a cost: the host schedules on stale info, so a
        request that finished inside the window rides one zombie step
        and its replacement joins one step late.  Harvesting first gives
        that staleness back in exactly the situations where fresh info
        outweighs the overlap of one step:

          * a queued request could join right now (free slot, admittable
            head): dispatch after joining — never block (returns False);
          * queue non-empty but nothing joinable: harvest if ANY active
            row may have finished inside the window (``output`` plus the
            window's maximum commits reaches its budget) — the finish
            would free a slot/blocks for the head;
          * empty queue (tail): harvest only when EVERY row may be done —
            dispatching then risks a step nobody needs.

        A scheduling heuristic only — outputs are byte-identical either
        way.  EOS finishes are not predicted (a surprise EOS costs at
        most one riding-along zombie row, which the static-shape step
        spans anyway).  With ``inflight=1`` the window is always empty
        here, so the synchronous loop is untouched.
        """
        rows = np.where(self._active)[0]
        if rows.size == 0:
            return False
        me = self._max_emit
        possibly_done = []
        for si in rows:
            r = self._slots[si]
            k = sum(1 for rec in self._inflight
                    if rec.active[si] and rec.slots[si] is r)
            possibly_done.append(
                len(r.output) + k * me >= r.max_new_tokens)
        if pending:
            if not self._active.all() and self._admit(pending[0]):
                return False
            return any(possibly_done)
        return all(possibly_done)

    # -- harvest (one step behind the dispatch frontier) ---------------------

    def _device_fed(self) -> None:
        """Close an open starvation window: device work was just queued,
        so the host is no longer serializing with the device."""
        if self._starve_t0 is not None:
            self.stats.host_stall_s += time.time() - self._starve_t0
            self._starve_t0 = None

    def _harvest(self, rec: _StepRecord) -> None:
        """Read one dispatched step's emissions and apply them to the
        requests it ran over (snapshotted in ``rec`` — host scheduling has
        moved on since dispatch).  This is the ONLY place the serve loop
        blocks on the device."""
        with TraceAnnotation("engine.read"):
            t0 = time.time()
            emitted = np.asarray(rec.emitted)       # blocks until the step
            n_em = np.asarray(rec.n_emitted)        # (and its joins) are done
            self.stats.read_wait_s += time.time() - t0
        with TraceAnnotation("engine.harvest"):
            self._apply_step(rec, emitted, n_em)

    def _apply_step(self, rec: _StepRecord, emitted: np.ndarray,
                    n_em: np.ndarray) -> None:
        """The host bookkeeping of one harvested step."""
        if not self._inflight and self._starve_t0 is None:
            # pipeline drained: host bookkeeping from here to the next
            # dispatch serializes with the (idle) device
            self._starve_t0 = time.time()

        # first tokens of the joins dispatched just before this step (the
        # step above already finished, so these reads are free now)
        for si, r, last_tok in rec.joins:
            ent = self._live_joins.get(si)
            if ent is None or ent[0] is not r:
                continue                # force-read early by a preemption
            del self._live_joins[si]
            self._absorb_first_token(r, int(np.asarray(last_tok)[si]))

        live = 0
        for si in np.where(rec.active)[0]:
            r = rec.slots[si]
            if not r.done:
                live += 1
                # the row's committed length when the step ran
                self.stats.kv_tokens_attended += (len(r.prompt)
                                                  + len(r.output) - 1)
                if self._slots[si] is r:    # still owns the slot (it may
                    self._advance(si, int(n_em[si]))   # have been preempted)
                appended = 0
                for t in emitted[si][:n_em[si]]:
                    # clamp at the budget: tokens past max_new_tokens are
                    # dropped even when accepted mid-step
                    if len(r.output) >= r.max_new_tokens:
                        break
                    r.output.append(int(t))
                    appended += 1
                    if r.eos_token is not None and t == r.eos_token:
                        r.done = True
                        break
                self.stats.tokens += appended
                if appended:
                    self._note_emission(r, appended)
                if r.done or len(r.output) >= r.max_new_tokens:
                    self._finish(r)
            # else: zombie row — finished before this (already-dispatched)
            # step was harvested; its emissions are discarded
            if r.done and self._slots[si] is r:
                self._slots[si] = None
                self._active[si] = False
                self._release(si)
        self.stats.steps += 1
        self.stats.active_slot_steps += live
        self.stats.capacity_slot_steps += rec.max_batch

    def _absorb_first_token(self, r: Request, tok0: int) -> bool:
        """Append a join's first sampled token; True if it finished the
        request outright (degenerate budget/EOS at t=0)."""
        self._note_first_token(r)
        r.output.append(tok0)
        if (len(r.output) >= r.max_new_tokens or
                (r.eos_token is not None and tok0 == r.eos_token)):
            self._finish(r)
            return True
        return False

    def _flush_join(self, si: int) -> None:
        """Force-read a not-yet-harvested join's first token.  A sync
        point, taken only when a just-joined slot is preempted before its
        first step harvests — without this the requeued request would be
        re-prefilled missing (or double-counting) its first token."""
        ent = self._live_joins.pop(si, None)
        if ent is None:
            return
        r, last_tok = ent
        with TraceAnnotation("engine.read"):
            t0 = time.time()
            tok0 = int(np.asarray(last_tok)[si])
            self.stats.read_wait_s += time.time() - t0
        self._absorb_first_token(r, tok0)

    def _drain_slot(self, si: int, r: Request) -> None:
        """Harvest every in-flight step in which slot ``si`` ran request
        ``r``.  Preemption calls this so ``r.output`` is complete before
        the request is requeued (resume re-prefills prompt + output)."""
        while any(rec.active[si] and rec.slots[si] is r
                  for rec in self._inflight):
            self._harvest(self._inflight.popleft())

    def _finish(self, r: Request) -> None:
        r.done = True
        r.t_done = time.time()
        self.stats.request_latency_s.append(r.latency_s)


class PagedSpeculativeEngine(SpeculativeEngine):
    """Continuous batching over a paged KV cache (DESIGN.md §6).

    Same scheduler and byte-identical greedy outputs as
    ``SpeculativeEngine``, but attention caches live in a global block
    pool of ``num_blocks × block_size`` cache positions instead of dense
    ``max_batch × max_len`` stripes.  ``num_blocks=None`` sizes the pool
    to the dense equivalent (no oversubscription); passing a smaller pool
    oversubscribes HBM and relies on:

      * **admission control** — a queued request joins only when its
        initial coverage (padded prompt + verify scratch) fits the free
        list; the queue head blocks the tail (strict FIFO);
      * **growth** — before every step each active slot's table is grown
        to cover ``cache_len + scratch``;
      * **preemption** — when growth exhausts the pool, the most recently
        joined slot is evicted: blocks freed, request requeued at the
        FRONT, resumed later by re-prefilling prompt + output-so-far
        (byte-exact under greedy; under sampling the resumed request
        draws fresh randomness).

    Per-request worst-case footprint must fit the pool outright (checked
    up front), which guarantees a lone slot can always grow — preemption
    therefore always makes progress.  Recurrent-state groups stay dense
    per-slot (O(1) each, nothing to page).

    Under the async loop (``inflight>=2``, DESIGN.md §7) every allocator
    decision runs in the pre-dispatch phase against host state that is
    one step stale, so join/growth/admission each budget
    ``_stale_allowance`` extra positions — coverage for the tokens the
    in-flight step may commit before its harvest lands.  Freed blocks
    can be re-handed out while a step still holding the old table is in
    flight: device program order makes that safe (the old step's writes
    complete before any later prefill/commit that could read the block —
    see §7 for the full argument).

    ``paged_attention="native"`` (default) runs the step's verify
    attention with the block-table-aware ``tree_attention_paged`` Pallas
    kernel and commits through the table — per-step transient memory is
    O(max_batch × T), not the dense view.  ``"shim"`` restores the old
    gather/scatter data path (parity oracle / triage only).

    With ``prefill_chunk`` (§8) prefill is a native pool consumer too:
    chunks scatter through the table (no dense join strip), blocks are
    allocated incrementally — one chunk's real tokens at a time — and
    admission is priced per chunk, so a long prompt starts prefilling as
    soon as one chunk's blocks are free instead of waiting for its whole
    footprint.  Pool exhaustion mid-prefill evicts the most recent
    joiner (possibly the prefilling slot itself — its partial prefill is
    discarded and byte-exactly recomputed on resume).
    """

    def __init__(self, params, draft_params, cfg: ModelConfig, tree, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 paged_attention: str = "native", **kw):
        super().__init__(params, draft_params, cfg, tree, **kw)
        self.block_size = int(block_size)
        self.blocks_per_slot = -(-self.max_len // self.block_size)   # M
        self.num_blocks = num_blocks   # None => dense-equivalent, see serve
        if paged_attention not in ("native", "shim"):
            raise ValueError(f"paged_attention must be 'native' or 'shim': "
                             f"{paged_attention}")
        # "native": stream pool blocks through the tree_attention_paged
        # kernel (the serving path).  "shim": gather/scatter the dense view
        # around the unmodified dense step — parity oracle / triage only.
        self.paged_attention = paged_attention
        greedy = self.criterion == "greedy"
        cfg_, tree_ = self.cfg, self.tree
        if self.use_speculative:
            self._step = jax.jit(_named(
                "verify_step",
                lambda p, dp, st, tbl, act: paged_spec_decode_step(
                    p, dp, cfg_, tree_, st, tbl, criterion=self.criterion,
                    temperature=self.temperature, epsilon=self.epsilon,
                    active=act, attention=paged_attention)))
        else:
            self._step = jax.jit(_named(
                "decode_step",
                lambda p, _dp, st, tbl, act: paged_autoregressive_step(
                    p, cfg_, st, tbl, greedy=greedy,
                    temperature=self.temperature, active=act,
                    attention=paged_attention)))
        self._join_fn = jax.jit(_named(
            "join", lambda p, dp, st, prompt, rl, slot, row: paged_join_slot(
                p, dp, cfg_, st, prompt, rl, slot, row, greedy=greedy)))
        # chunked prefill writes straight through the block table — the
        # per-slot dense join strip never exists on this path (§8).  The
        # view extent arrives as a static TABLE-ROW truncation (blocks)
        self._chunk_fns = {
            fin: jax.jit(_named(
                _CHUNK_NAMES[fin],
                lambda p, dp, st, ch, start, rl, slot, row, vb, _f=fin:
                paged_join_slot_chunk(p, dp, cfg_, st, ch, start, rl, slot,
                                      row, final=_f, view_blocks=vb,
                                      greedy=greedy)),
                static_argnums=8)
            for fin in (False, True)} if self.prefill_chunk else {}

    # -- jitted-call adapters (block table rides along as an operand) --------

    def _run_step(self, state, active=None):
        return self._step(self.params, self.draft_params, state,
                          _snapshot(self._tables), active)

    def _join(self, state, slot: int, r: Request):
        padded, n = self._padded_context(r)
        got = self._alloc.alloc(self._alloc.blocks_for(
            max(len(padded), n + self._scratch + self._stale_allowance)))
        assert got is not None, "_admit must have checked the free list"
        self._owned[slot] = got
        self._tables[slot, :] = NULL_BLOCK
        self._tables[slot, :len(got)] = got
        self._slot_len[slot] = n
        self._seq += 1
        self._join_seq[slot] = self._seq
        return self._join_fn(self.params, self.draft_params, state,
                             jnp.asarray(padded), jnp.int32(n),
                             jnp.int32(slot),
                             _snapshot(self._tables[slot]))

    def _warm_buckets(self, requests: List[Request]) -> set:
        buckets = super()._warm_buckets(requests)
        # chunked prefill resumes with the same two chunk executables —
        # no per-bucket warm needed (super() already returned empty)
        if (self.num_blocks is not None and self.prefill_bucket > 1
                and not self.prefill_chunk):
            # preemption can resume a request with context up to
            # prompt + budget - 1 tokens: precompile every bucket a resume
            # could land in so the retrace never runs inside the clock.
            # (Exact-length-prefill archs — prefill_bucket == 1 — would
            # need one compile per possible length; there a resume pays
            # its own compile instead, like any new prompt length does.)
            for r in requests:
                lo = self._pad_len(len(r.prompt))
                hi = self._pad_len(len(r.prompt) + r.max_new_tokens - 1)
                buckets.update(range(lo, hi + 1, self.prefill_bucket))
        return buckets

    def _warm_join(self, state, P: int):
        # an all-NULL table row: warmup results are discarded, and the NULL
        # block absorbs the garbage prefill writes
        return self._join_fn(self.params, self.draft_params, state,
                             jnp.zeros(P, jnp.int32), jnp.int32(1),
                             jnp.int32(0),
                             jnp.zeros(self.blocks_per_slot, jnp.int32))

    # -- chunked prefill over the pool (§8) ----------------------------------

    def _view_blocks(self, view: int) -> int:
        return min(-(-view // self.block_size), self.blocks_per_slot)

    def _dispatch_chunk(self, state, si: int, chunk: np.ndarray, start: int,
                        real_len: int, final: bool):
        view = self._chunk_view_len(start + self.prefill_chunk)
        return self._chunk_fns[final](
            self.params, self.draft_params, state, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(real_len), jnp.int32(si),
            _snapshot(self._tables[si]), self._view_blocks(view))

    def _warm_chunk(self, state, final: bool, view: int):
        # warm against an all-NULL table row (garbage absorbed, discarded)
        return self._chunk_fns[final](
            self.params, self.draft_params, state,
            jnp.zeros(self.prefill_chunk, jnp.int32), jnp.int32(0),
            jnp.int32(1), jnp.int32(0),
            jnp.zeros(self.blocks_per_slot, jnp.int32),
            self._view_blocks(view))

    def _admit_prefill(self, r: Request) -> bool:
        """Chunked admission is priced per chunk: only the FIRST chunk's
        real-token blocks must be free (plus the usual one-growth-block
        headroom per joined slot) — later chunks allocate as they
        dispatch, so a long prompt no longer has to find its whole
        footprint at once to start prefilling."""
        n = len(r.prompt) + len(r.output)
        need = self._alloc.blocks_for(min(self.prefill_chunk, n))
        headroom = sum(1 for o in self._owned if o)
        return need + headroom <= self._alloc.free_blocks

    def _grow_prefill(self, si: int, job: _PrefillJob, slots, active,
                      pending) -> bool:
        """Allocate blocks covering the next chunk's REAL tokens (final-
        chunk pads write to the NULL block and are never read).  On
        exhaustion, evict the most recent joiner — possibly ``si``
        itself, in which case the partial prefill is abandoned and the
        request requeued (the up-front capacity check guarantees a lone
        slot can always cover a whole request, so this terminates)."""
        cover = min(job.off + self.prefill_chunk, job.real_len)
        while True:
            need = self._alloc.blocks_for(cover) - len(self._owned[si])
            if need <= 0:
                return True
            got = self._alloc.alloc(need)
            if got is not None:
                base = len(self._owned[si])
                self._owned[si].extend(got)
                self._tables[si, base:base + len(got)] = got
                return True
            victims = [s for s in range(len(slots))
                       if active[s] or s in self._prefills]
            victim = max(victims, key=lambda s: self._join_seq[s])
            with TraceAnnotation("engine.preempt", rid=slots[victim].rid):
                self._preempt(int(victim), slots, active, pending)
            if victim == si:
                return False

    def _advance_prefill_cursor(self, si: int, n: int) -> None:
        self._slot_len[si] = n

    # -- block accounting ----------------------------------------------------

    def _pool_blocks(self, max_batch: int) -> int:
        return self.num_blocks or 1 + max_batch * self.blocks_per_slot

    def lower_step(self, max_batch: int):
        """The decode step ``serve(max_batch=...)`` dispatches, lowered
        ahead of time: ``.compile().as_text()`` shows the program the chip
        runs, e.g. whether its attention kernels compiled through Mosaic
        (``tpu_custom_call``) or were interpreted."""
        state = jax.eval_shape(lambda: init_paged_state(
            self.params, self.draft_params, self.cfg, max_batch,
            self._pool_blocks(max_batch), self.block_size,
            jax.random.PRNGKey(0)))
        table = jax.ShapeDtypeStruct((max_batch, self.blocks_per_slot),
                                     jnp.int32)
        active = jax.ShapeDtypeStruct((max_batch,), jnp.bool_)
        return self._step.lower(self.params, self.draft_params, state, table,
                                active)

    def _init_pool(self, max_batch: int, rng):
        nb = self._pool_blocks(max_batch)
        self._alloc = BlockAllocator(nb, self.block_size)
        B, M = max_batch, self.blocks_per_slot
        self._tables = np.zeros((B, M), np.int32)       # all rows -> NULL
        self._owned: List[List[int]] = [[] for _ in range(B)]
        self._slot_len = np.zeros(B, np.int64)          # committed tokens
        self._join_seq = np.zeros(B, np.int64)          # preemption order
        self._seq = 0
        st = self.stats
        st.block_size = self.block_size
        st.num_blocks = nb
        st.pool_tokens = (nb - 1) * self.block_size
        st.dense_equiv_tokens = max_batch * self.max_len
        # under "native" every group — full-attention, sliding-window and
        # MLA alike — streams the pool through an attention-template
        # instantiation (models/model.py dispatch), so the step transient
        # is just the scratch writes; only the "shim" oracle still
        # materializes the per-slot logical view
        st.step_transient_tokens = max_batch * (
            self._scratch
            if self.paged_attention == "native"
            and paged_kernel_covers(self.cfg)
            else self.blocks_per_slot * self.block_size)
        return init_paged_state(self.params, self.draft_params, self.cfg,
                                max_batch, nb, self.block_size, rng)

    def _check_capacity(self, r: Request) -> None:
        # worst-case lifetime coverage: the (padded) resumed context can
        # reach prompt+budget tokens, plus one verify-scratch region,
        # plus the async staleness margin growth budgets per step
        worst = (self._pad_len(len(r.prompt) + r.max_new_tokens)
                 + self._scratch + self._stale_allowance)
        view_len = self.blocks_per_slot * self.block_size
        if worst > view_len:
            raise ValueError(
                f"request needs {worst} cache slots but the per-slot view "
                f"caps at {view_len} (max_len={self.max_len})")
        if self.num_blocks is not None:
            need = -(-worst // self.block_size)
            usable = self.num_blocks - 1
            if need > usable:
                raise ValueError(
                    f"request needs {need} cache blocks at its peak but the "
                    f"pool only has {usable} usable blocks "
                    f"(num_blocks={self.num_blocks} incl. the NULL block)")

    def _admit(self, r: Request) -> bool:
        n = len(r.prompt) + len(r.output)
        need = self._alloc.blocks_for(
            max(self._pad_len(n), n + self._scratch + self._stale_allowance))
        # headroom: keep one growth block per already-joined slot, so
        # admitting this request doesn't immediately force a preemption
        # (which would thrash: evict, readmit, re-prefill, evict ...).
        # With no joined slots the headroom is zero, so the up-front
        # worst-case check keeps the pool deadlock-free.
        headroom = sum(1 for o in self._owned if o)
        return need + headroom <= self._alloc.free_blocks

    def _before_step(self, state, slots, active, pending):
        """Grow every active slot's table to cover the coming step's
        scratch region — PLUS the stale allowance, since under the async
        loop ``_slot_len`` lags the device by the in-flight step's
        commits; preempt newest-first when the pool runs dry."""
        order = sorted(np.where(active)[0], key=lambda s: self._join_seq[s])
        for si in order:
            # re-checked every round: a _preempt below may evict si itself
            # OR its drain may harvest si's finish and release it — growing
            # a released slot would orphan the blocks at the next join
            while active[si]:
                need = (self._alloc.blocks_for(
                    int(self._slot_len[si]) + self._scratch
                    + self._stale_allowance)
                    - len(self._owned[si]))
                if need <= 0:
                    break
                got = self._alloc.alloc(need)
                if got is not None:
                    base = len(self._owned[si])
                    self._owned[si].extend(got)
                    self._tables[si, base:base + len(got)] = got
                    break
                # prefilling slots are eviction candidates too — they hold
                # blocks and are usually the most recent joiners
                victims = [s for s in range(len(slots))
                           if active[s] or s in self._prefills]
                victim = max(victims, key=lambda s: self._join_seq[s])
                with TraceAnnotation("engine.preempt",
                                     rid=slots[victim].rid):
                    self._preempt(int(victim), slots, active, pending)
        return state

    def _preempt(self, si: int, slots, active, pending) -> None:
        r = slots[si]
        job = self._prefills.pop(si, None)
        if job is not None:
            # mid-prefill eviction (§8): the victim never activated, so
            # no step ran it and no join token is pending — just free its
            # blocks and requeue; the resume restarts from chunk 0 (the
            # partial prefill is discarded, byte-exactly recomputed)
            slots[si] = None
            self._release(si)
            pending.appendleft(r)
            self.stats.preemptions += 1
            return
        # async: the victim's output must be complete before it is
        # requeued (resume re-prefills prompt + output).  Force-read its
        # join if unharvested, then drain every in-flight step it ran in
        # — the only sync points the async loop takes, both rare, both on
        # the already-expensive eviction path.
        self._flush_join(si)
        self._drain_slot(si, r)
        if slots[si] is not r:
            # the drain discovered the request finished (budget/EOS) and
            # already released the slot — nothing left to evict
            active[si] = False
            return
        slots[si] = None
        active[si] = False
        self._release(si)
        if not r.done:
            pending.appendleft(r)           # resume ASAP, FIFO preserved
            self.stats.preemptions += 1

    def _advance(self, slot: int, n_tokens: int) -> None:
        self._slot_len[slot] += n_tokens    # host mirror of cache_len

    def _release(self, slot: int) -> None:
        if self._owned[slot]:
            self._alloc.free(self._owned[slot])
            self._owned[slot] = []
        self._tables[slot, :] = NULL_BLOCK
        self._slot_len[slot] = 0

    def _post_serve(self) -> None:
        self.stats.peak_blocks_in_use = max(self.stats.peak_blocks_in_use,
                                            self._alloc.peak_in_use)


class BucketedEngine(_EngineBase):
    """Legacy static scheduler: exact-prompt-length buckets, run to
    completion.  Kept as the measured baseline for the continuous engine."""

    # -- batching ------------------------------------------------------------

    @staticmethod
    def bucket(requests: List[Request], max_batch: int):
        by_len: dict = {}
        for r in requests:
            by_len.setdefault(len(r.prompt), []).append(r)
        for _, group in sorted(by_len.items()):
            for i in range(0, len(group), max_batch):
                yield group[i:i + max_batch]

    # -- serving -------------------------------------------------------------

    def serve(self, requests: List[Request], *, max_batch: int = 8,
              warmup: bool = True) -> EngineStats:
        scratch = self.tree.size if self.use_speculative else 1
        batches = list(self.bucket(requests, max_batch))
        for batch in batches:
            # a finished row keeps stepping until its whole batch drains, so
            # capacity must cover the LARGEST budget in the batch per row
            need = (len(batch[0].prompt)
                    + max(r.max_new_tokens for r in batch) + scratch)
            if need > self.max_len:
                raise ValueError(
                    f"batch needs {need} cache slots but "
                    f"max_len={self.max_len}")
        if warmup:  # precompile prefill+step per batch signature
            for batch in batches:
                B, P = len(batch), len(batch[0].prompt)
                st = init_decode_state(
                    self.params,
                    self.draft_params if self.use_speculative else None,
                    self.cfg, jnp.zeros((B, P), jnp.int32), self.max_len,
                    jax.random.PRNGKey(0),
                    greedy=(self.criterion == "greedy"))
                jax.block_until_ready(self._run_step(st).state.cache_len)
        # enqueue AFTER warmup so latency measures serving, not XLA compiles
        now = time.time()
        for r in requests:
            r.t_enqueue = now
        for batch in batches:
            self._serve_batch(batch, max_batch, warmup=False)
        return self.stats

    def _serve_batch(self, batch: List[Request], max_batch: int,
                     warmup: bool) -> None:
        prompts = jnp.asarray(np.stack([r.prompt for r in batch]))
        self.rng, sub = jax.random.split(self.rng)
        state = init_decode_state(
            self.params, self.draft_params if self.use_speculative else None,
            self.cfg, prompts, self.max_len, sub,
            greedy=(self.criterion == "greedy"))
        for r, t in zip(batch, np.asarray(state.last_token)):
            r.t_join = time.time()
            self._note_first_token(r)
            r.output.append(int(t))
            if (len(r.output) >= r.max_new_tokens or
                    (r.eos_token is not None and int(t) == r.eos_token)):
                self._finish(r)

        budget = max(r.max_new_tokens for r in batch)

        if warmup:  # compile outside the timed region
            jax.block_until_ready(self._run_step(state).state.cache_len)

        produced = 1
        t0 = time.time()
        t_read_end = None
        while produced < budget and not all(r.done for r in batch):
            res = self._run_step(state)
            if t_read_end is not None:
                # fully synchronous baseline: all host bookkeeping since
                # the last read ran against an idle device
                self.stats.host_stall_s += time.time() - t_read_end
            state = res.state
            t_sync = time.time()
            jax.block_until_ready(state.cache_len)
            emitted = np.asarray(res.emitted)
            n_em = np.asarray(res.n_emitted)
            t_read_end = time.time()
            self.stats.read_wait_s += t_read_end - t_sync
            # fully synchronous scheduler: exactly one step ever in flight
            self.stats.steps_in_flight = max(self.stats.steps_in_flight, 1)
            live = np.array([not r.done for r in batch])
            for bi, r in enumerate(batch):
                if r.done:
                    continue  # finished rows keep stepping but emit nothing
                self.stats.kv_tokens_attended += (len(r.prompt)
                                                  + len(r.output) - 1)
                appended = 0
                for t in emitted[bi][:n_em[bi]]:
                    if len(r.output) >= r.max_new_tokens:
                        break  # clamp the output at the request budget
                    r.output.append(int(t))
                    appended += 1
                    if r.eos_token is not None and t == r.eos_token:
                        r.done = True
                        break
                self.stats.tokens += appended
                if appended:
                    self._note_emission(r, appended)
                if r.done or len(r.output) >= r.max_new_tokens:
                    self._finish(r)
            self.stats.steps += 1
            self.stats.active_slot_steps += int(live.sum())
            self.stats.capacity_slot_steps += max_batch
            produced += int(n_em.min()) if n_em.size else 1
        self.stats.wall_s += time.time() - t0

    def _finish(self, r: Request) -> None:
        if r.t_done is not None:
            return
        r.done = True
        r.t_done = time.time()
        self.stats.request_latency_s.append(r.latency_s)
