"""Continuous batching: slot-refill scheduler + chunked prefill admission.

The paper's throughput discipline — pack once at load, keep every
compute block busy with fine panels each step — dies in a serving loop
that phase-locks a static batch: one slow request holds ``batch_slots``
slots hostage for ``max_new_tokens`` steps.  This module replaces that
loop with a real scheduler over a *static-shape* slot pool:

  * **Slot refill mid-generation.**  Requests queue FIFO; a slot whose
    request finishes is freed and refilled immediately.  Shapes never
    change — the decode step is always ``[batch_slots, 1]`` with a
    per-slot length vector and write mask — so nothing recompiles and no
    GEMM replans (``plan_cache_info().misses`` is flat in steady state).
  * **Paged KV** (runtime/kv_cache): a refilled slot reuses the pages its
    predecessor freed instead of re-allocating ``[B, max_len]``.
  * **Chunked prefill admission.**  New prompts prefill in fixed-width
    chunks (padded to a ``gemm.bucket_m`` bucket) interleaved with decode
    steps, so admission never stalls decode for a whole prompt and the
    K>=N fine-panel plans stay hot across both phases.

Scheduling is host-side and deliberately simple: per tick, (1) enforce
deadlines/cancellations, (2) admit from the queue into idle slots while
the page budget holds, (3) run one prefill chunk for the
earliest-admitted prefilling slot, (4) run one decode step for every
decoding slot.  The device work is the Engine's jitted
``prefill_chunk`` / ``decode_step``; this module never traces.

**Fault isolation** (the serving analogue of the paper's guarantee
discipline): every request carries a lifecycle state
(``RequestState``: QUEUED/RUNNING/DONE/FAILED/CANCELLED/TIMED_OUT) and
a structured :class:`RequestOutcome`; a fault is confined to the
requests it actually hits.  A dispatch exception walks a degradation
ladder — retry once on the engine's backend, then one attempt on the
``xla`` fallback backend (bit-exact, because every registered backend
passes the same gate) — and only then quarantines the victim: the
poisoned request's pages are freed (refcounts keep shared prefix pages
safe), its slot is recycled, and the batch continues.  Because batched
greedy decode is row-independent (each slot attends only to its own
pages and masked rows write nothing), **survivors stay bit-identical
to a fault-free run** — the gate ``tests/test_chaos.py`` enforces
under injected faults (runtime/faults).

Outputs are bit-identical to per-request greedy ``Engine.generate`` —
the serving analogue of the paper's bit-exactness gate, enforced by
tests/test_serving.py.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import gemm as gemm_api
from repro.obs import metrics as _metrics
from repro.obs import spans as _spans
from repro.obs.timing import FencedTimer
from repro.runtime import fault_tolerance as FT
from repro.runtime import faults
from repro.runtime import kv_cache as KV
from repro.runtime.prefix_cache import PrefixCache, PrefixCacheStats


# --------------------------------------------------------------- lifecycle
class RequestState(str, enum.Enum):
    """Per-request lifecycle.  Terminal states other than DONE carry a
    structured reason in the request's :class:`RequestOutcome`."""
    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    DONE = "DONE"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"


@dataclasses.dataclass
class RequestOutcome:
    """The structured per-request result record — every submitted
    request ends in exactly one of these, fault or not.

    ``tokens``: the full output for DONE requests; for evicted requests
    the tokens emitted before the fault (None if none).  ``error`` /
    ``error_type`` describe the terminal reason for non-DONE states."""
    rid: int
    state: RequestState
    prompt_len: int
    emitted: int = 0
    tokens: np.ndarray | None = None
    error: str | None = None
    error_type: str | None = None


class RejectedError(RuntimeError):
    """Admission refused (bounded queue overflow, or shutdown drain).
    ``snapshot`` carries the queue/slot/page-pool state at rejection —
    the backpressure signal a front-end load-sheds on."""

    def __init__(self, msg: str, *, snapshot: dict):
        super().__init__(msg)
        self.snapshot = snapshot


class SchedulerStallError(RuntimeError):
    """The tick loop exhausted its progress bound — a scheduler bug,
    not load.  ``snapshot`` carries the queue/slot/page-pool state so
    the stall is diagnosable instead of a bare "no progress"."""

    def __init__(self, msg: str, *, snapshot: dict):
        super().__init__(f"{msg}; state: {snapshot}")
        self.snapshot = snapshot


# ------------------------------------------------------------------ stats
@dataclasses.dataclass
class RequestStats:
    """Per-request serving latency record (all seconds / tokens).

    ``emit_s`` holds each emitted token's time since enqueue on the
    scheduler's clock (the first entry is the TTFT); ``itl_s`` are the
    gaps between consecutive tokens.  A megastep drain of D ticks emits
    D tokens at one time, so its inner gaps read 0.  Under
    ``sync_per_step`` a token's time falls after the device finished
    the step that made it; under async dispatch, after the dispatch."""
    rid: int
    prompt_len: int
    new_tokens: int
    queue_wait_s: float          # enqueue -> admitted to a slot
    ttft_s: float                # enqueue -> first token emitted
    total_s: float               # enqueue -> finished
    decode_tps: float            # new_tokens over first-token -> finish
    emit_s: list = dataclasses.field(default_factory=list)

    @property
    def itl_s(self) -> list:
        return [b - a for a, b in zip(self.emit_s, self.emit_s[1:])]


class StepRecord(NamedTuple):
    """One scheduler ``step()``: its start on ``time.perf_counter`` (s),
    its wall time, the part of it spent in fences (the host waiting on
    the device; 0 without ``sync_per_step``), and the host-to-device
    uploads it made (page tables, lengths, chunks, masks)."""
    t0: float
    wall_ms: float
    fence_ms: float
    uploads: int


@dataclasses.dataclass
class ServeStats:
    """Aggregate + per-request serving stats.

    Token counts follow the live-slot, non-pad discipline:
    ``prefill_tokens`` counts true prompt tokens actually COMPUTED
    (never chunk padding or dead slots — and never positions the
    prefix cache served from shared pages; those are in
    ``prefix.hit_tokens``); ``decode_tokens`` counts tokens actually
    emitted to a request (the first, prefill-sampled token included).

    ``prefix`` (``prefix_cache=True`` runs only) carries the
    cross-request prefix cache's hit/evict/COW counters
    (:class:`repro.runtime.prefix_cache.PrefixCacheStats`).

    GEMM-dispatch observability: ``plan_cache`` snapshots
    ``gemm.plan_cache_info()`` at run end (plan churn — misses moving in
    steady state means chunk bucketing broke) and ``vmem_clamped_plans``
    counts cached plans whose blocks the policy shrank to fit the
    kernel VMEM budget; ``plan_store`` snapshots the engine's persistent
    plan-store counters (``gemm.StoreInfo``; None when the engine runs
    without a store); ``quant`` is the engine's quantized weight
    format (None: fp32).

    Per-phase latency breakdown (the decode fast lane's observability):
    ``prefill_tick_ms`` / ``decode_tick_ms`` record every tick's
    dispatch duration (a megastep drain of D ticks contributes D
    entries of drain/D — under ``sync_per_step`` these are exact
    device times, under async they are dispatch times); query p50/p99
    via :meth:`phase_percentile`.  ``steps`` holds a
    :class:`StepRecord` per scheduler ``step()``: its wall time
    (``step_ms``) beside the time its fences waited, so a step's host
    time is the difference, and the uploads it made.
    ``decode_dispatches`` counts device
    decode calls (``decode_ticks / decode_dispatches`` ~= the realized
    megastep depth), ``host_syncs`` counts the host-blocking
    synchronization points the run actually paid (every
    ``sync_per_step`` block + the final materialize) and
    ``megastep_depth`` echoes the configured D.

    Expert load (MoE configs): ``expert_rows`` holds,
    per paged dispatch, ``(phase, rows)`` with rows ``[MoE layers,
    held experts]`` int32 — the rows the step routed to each expert this
    chip holds — left on the device until read (:meth:`expert_rows_of`).

    Fault-isolation observability: ``outcomes`` maps rid to
    :class:`RequestOutcome` (every submitted request, terminal states
    included); ``dispatch_retries`` / ``backend_fallbacks`` count the
    degradation ladder's rungs; ``degraded`` counts graceful
    degradations by reason (e.g. ``prefix_lookup`` — a prefix-cache
    error served cold); ``stragglers`` holds the serving watchdog's
    :class:`~repro.runtime.fault_tolerance.StragglerEvent` records
    (``watchdog_factor`` runs only); ``trace_dropped`` counts audit-log
    events the bounded scheduler trace dropped (oldest first) once past
    its cap.
    """
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    wall_s: float = 0.0
    fused: bool | None = None       # engine ran the fused GEMM path
    quant: str | None = None        # engine's quantized weight format
    quant_density: float | None = None   # mean occupied-group fraction
    quant_sparse_packs: int = 0     # packs on the compressed layout
    plan_cache: tuple | None = None
    vmem_clamped_plans: int = 0
    plan_store: tuple | None = None
    requests: list[RequestStats] = dataclasses.field(default_factory=list)
    prefill_tick_ms: list = dataclasses.field(default_factory=list)
    decode_tick_ms: list = dataclasses.field(default_factory=list)
    steps: list[StepRecord] = dataclasses.field(default_factory=list)
    decode_dispatches: int = 0
    host_syncs: int = 0
    megastep_depth: int = 1
    prefix: PrefixCacheStats | None = None
    outcomes: dict = dataclasses.field(default_factory=dict)
    dispatch_retries: int = 0
    backend_fallbacks: int = 0
    degraded: dict = dataclasses.field(default_factory=dict)
    stragglers: list = dataclasses.field(default_factory=list)
    trace_dropped: int = 0
    expert_rows: list = dataclasses.field(default_factory=list)

    def expert_rows_of(self, phase: str | None = None) -> np.ndarray:
        """``[dispatches, MoE layers, held]`` int32 of ``phase``'s
        dispatches ("prefill" | "decode"; None: both), in order."""
        rows = [np.asarray(r) for p, r in self.expert_rows
                if phase is None or p == phase]
        return np.stack(rows) if rows else np.zeros((0, 0, 0), np.int32)

    @property
    def prefill_tps(self):
        return self.prefill_tokens / max(self.prefill_s, 1e-9)

    @property
    def decode_tps(self):
        return self.decode_tokens / max(self.decode_s, 1e-9)

    @property
    def total_tps(self):
        """Emitted tokens over wall time — the cross-engine comparable."""
        return self.decode_tokens / max(self.wall_s, 1e-9)

    @property
    def decode_ticks(self) -> int:
        return len(self.decode_tick_ms)

    @property
    def step_ms(self) -> list:
        return [r.wall_ms for r in self.steps]

    @property
    def uploads(self) -> int:
        return sum(r.uploads for r in self.steps)

    @property
    def completed(self) -> int:
        return sum(1 for o in self.outcomes.values()
                   if o.state == RequestState.DONE)

    @property
    def failed(self) -> int:
        """Requests in a terminal state other than DONE."""
        return sum(1 for o in self.outcomes.values()
                   if o.state not in (RequestState.DONE,
                                      RequestState.QUEUED,
                                      RequestState.RUNNING))

    def percentile(self, field: str, q: float) -> float:
        vals = [getattr(r, field) for r in self.requests]
        return float(np.percentile(vals, q)) if vals else 0.0

    def phase_percentile(self, phase: str, q: float) -> float:
        """Percentile (ms) over per-tick durations of ``phase``
        ("prefill" | "decode")."""
        vals = {"prefill": self.prefill_tick_ms,
                "decode": self.decode_tick_ms}[phase]
        return float(np.percentile(vals, q)) if vals else 0.0


class _BoundedTrace:
    """The scheduler's audit log, bounded (ISSUE 9 satellite: the bare
    ``list`` grew without limit — a long-lived scheduler leaked memory
    at one tuple per event forever).  Drops the OLDEST events past
    ``cap`` and counts them in ``dropped`` (surfaced as
    ``ServeStats.trace_dropped`` and the ``serve_trace_dropped``
    metric), so the recent window the invariant audits replay stays
    intact while the log stops growing.  The cap is deliberately far
    above any test run's event count — the audits see complete logs."""

    __slots__ = ("cap", "dropped", "_buf")

    def __init__(self, cap: int = 100_000):
        self.cap = cap
        self.dropped = 0
        self._buf: collections.deque[tuple] = collections.deque(maxlen=cap)

    def append(self, ev: tuple) -> None:
        if len(self._buf) == self.cap:
            self.dropped += 1
        self._buf.append(ev)

    def __iter__(self):
        return iter(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return list(self._buf)[idx]
        return self._buf[idx]


@dataclasses.dataclass
class _Request:
    rid: int
    tokens: np.ndarray
    max_new: int
    t_enqueue: float
    t_admit: float = 0.0
    t_first: float | None = None
    ttft_budget_s: float | None = None
    total_budget_s: float | None = None
    cancel: bool = False
    emitted_at: list = dataclasses.field(default_factory=list)


class _Slot:
    __slots__ = ("request", "n_prefilled", "n_emitted", "first_tok",
                 "steps", "order")

    def __init__(self):
        self.request: _Request | None = None
        self.n_prefilled = 0
        self.n_emitted = 0
        self.first_tok = None      # device scalar from the final chunk
        self.steps: list[int] = []  # indices into the decode history
        self.order = -1            # admission sequence number (FIFO tie)

    @property
    def prefill_done(self):
        return (self.request is not None
                and self.n_prefilled == len(self.request.tokens))


# -------------------------------------------------------------- scheduler
class ContinuousBatchingScheduler:
    """Drives an Engine's paged ``prefill_chunk`` / ``decode_step`` over a
    FIFO request queue with slot refill.

    ``engine`` needs: ``cfg``, ``max_len``, and the two paged step
    methods — the invariant tests drive the scheduler with a stub engine
    to cover thousands of schedules without tracing.  Engines exposing
    ``supports_fallback`` additionally accept ``fallback=True`` on the
    paged steps (the ``xla``-backend escape hatch the dispatch
    degradation ladder uses).

    ``num_pages`` below the dense-equivalent total turns on real paging
    pressure: admission then waits until finished requests return enough
    pages (the reservation check keeps the pool deadlock-free — a request
    is only admitted when its *whole* worst-case footprint fits alongside
    the outstanding growth of every live slot).

    The token feedback loop stays on device: completion is a *count*
    (max_new), never a token value, so the scheduler dispatches steps
    without a host sync and materializes outputs once at the end — the
    same async pipelining ``generate`` gets from its device-side loop.
    ``sync_per_step=True`` blocks after every device call instead, making
    the per-phase timings and TTFT exact (the launcher's percentile
    report uses it); under async they are dispatch-time measurements.

    ``megastep_depth`` (D > 1) drains decode through the engine's fused
    megastep: up to D decode ticks run device-side per host dispatch
    (``Engine.decode_megastep`` — one jitted ``lax.fori_loop`` carrying
    greedy argmax, paged KV writes and the next-token embed), and the
    scheduler drains the emitted tokens every D ticks.  The realized
    depth of each drain is ``min(D, smallest remaining token budget
    among decoding slots)``, so no slot ever over-generates: the event
    trace, exactly-once completion and ``serve == generate`` bitwise
    parity hold at every depth (each megastep tick is the same jitted
    computation as a per-tick dispatch).  The trade: admission and
    chunked prefill interleave only at drain boundaries, so deep
    megasteps buy dispatch amortization at some TTFT cost
    (docs/serving.md).

    ``prefix_cache=True`` turns on the cross-request prefix cache
    (runtime/prefix_cache): admission looks the prompt up in a radix
    index over the page pool, installs the matched pages into the
    slot's table by reference (COW-forking the divergence page), and
    starts chunked prefill at the first uncovered token; a prompt's
    full pages are indexed once its prefill completes, and the index's
    LRU sweep is the pool's pressure evictor.  The cache lives as long
    as this scheduler — ``run`` may be called repeatedly and later
    requests hit earlier runs' prefixes.  Outputs stay bit-identical
    to per-request ``generate`` (the cached KV is bitwise what this
    request's own prefill would have written).  A prefix-cache error
    (lookup/admit/insert) never fails the request: the scheduler
    degrades to cold prefill and counts the reason in
    ``ServeStats.degraded``.

    Fault-isolation knobs: ``max_queue`` bounds the admission queue
    (``submit`` past the bound raises :class:`RejectedError` with a
    state snapshot — load shedding); ``watchdog_factor`` arms a
    :class:`~repro.runtime.fault_tolerance.StepWatchdog` over scheduler
    ticks (straggler events land in ``ServeStats.stragglers``);
    ``shutdown`` takes an object with a ``requested`` flag (a
    ``GracefulShutdown``) — once set, queued requests are drained to
    CANCELLED("shutdown") outcomes, new submissions are rejected, and
    in-flight requests run to completion; ``clock`` injects a fake
    monotonic clock for deterministic deadline tests (device timing
    stats always use the real clock).  Per-request deadlines ride on
    ``submit(..., ttft_budget_s=, total_budget_s=)`` and are enforced
    at tick boundaries, as is cooperative :meth:`cancel`.

    ``trace`` records ``(event, ...)`` tuples — the scheduler's own audit
    log, asserted over by the serving invariant tests.  It is BOUNDED
    (:class:`_BoundedTrace`): past the cap the oldest events are dropped
    and counted (``ServeStats.trace_dropped``), so a long-lived
    scheduler never grows its log without limit.  ``run`` ends
    with the pool's ``assert_all_free`` leak audit — on the success
    path AND on every exception path (try/finally): with every request
    freed, a page refcount that never returned to zero (possible only
    through a sharing bug) raises instead of leaking silently.
    """

    def __init__(self, engine, *, batch_slots: int, prefill_chunk: int = 32,
                 page_size: int = 16, num_pages: int | None = None,
                 check_invariants: bool = False,
                 sync_per_step: bool = False, megastep_depth: int = 1,
                 prefix_cache: bool = False, max_queue: int | None = None,
                 watchdog_factor: float | None = None, shutdown=None,
                 clock=None):
        cfg = engine.cfg
        if cfg.modality != "text":
            raise NotImplementedError("continuous batching serves token "
                                      "prompts; stub-embedding frontends "
                                      "go through Engine.prefill")
        self.engine = engine
        self.cfg = cfg
        self.batch_slots = batch_slots
        self.page_size = page_size
        # static admission width: pad to a plan bucket so every chunk in
        # a mixed-length stream resolves the same GEMM plan keys
        self.chunk = gemm_api.bucket_m(prefill_chunk)
        self.check_invariants = check_invariants
        self.sync_per_step = sync_per_step
        if megastep_depth < 1:
            raise ValueError(f"megastep_depth={megastep_depth}: need >= 1")
        if megastep_depth > 1 and not hasattr(engine, "decode_megastep"):
            raise ValueError("megastep_depth > 1 needs an engine with "
                             "decode_megastep (Engine, or a stub "
                             "providing it)")
        self.megastep_depth = megastep_depth
        self.max_queue = max_queue
        self.watchdog = (FT.StepWatchdog(factor=watchdog_factor)
                         if watchdog_factor else None)
        self._shutdown = shutdown
        self._draining = False
        self._clock = clock if clock is not None else time.perf_counter
        self.kv = KV.PagedKVCache(
            num_layers=cfg.num_layers, num_slots=batch_slots,
            max_len=engine.max_len, page_size=page_size,
            leaf_specs=KV.leaf_specs_for(cfg), num_pages=num_pages)
        self.prefix = PrefixCache(self.kv) if prefix_cache else None
        self.slots = [_Slot() for _ in range(batch_slots)]
        self.queue: collections.deque[_Request] = collections.deque()
        self.trace = _BoundedTrace()
        self.stats = ServeStats(megastep_depth=megastep_depth)
        self.outcomes = self.stats.outcomes        # rid -> RequestOutcome
        self._results: dict[int, np.ndarray] = {}
        self._next_rid = 0
        self._admit_seq = 0
        self._ticks = 0
        # the running step's fence seconds and uploads (StepRecord)
        self._step_fence_s = 0.0
        self._step_uploads = 0
        # device-side run state: last emitted token per slot, and the
        # per-step [slots] token history (materialized at run end)
        self._last = jnp.zeros((batch_slots,), jnp.int32)
        self._history: list = []
        self._pending: list[tuple] = []   # (rid, slot, first_tok, steps)

    # ------------------------------------------------------------ intake
    def submit(self, tokens, max_new: int, *,
               ttft_budget_s: float | None = None,
               total_budget_s: float | None = None) -> int:
        """Enqueue one request; returns its rid.  ``ttft_budget_s`` /
        ``total_budget_s`` are per-request deadlines (enqueue-relative,
        enforced at tick boundaries — a request whose first token
        misses its TTFT budget, or whose wall clock exceeds its total
        budget, is evicted as TIMED_OUT with partial tokens in its
        outcome).  Raises :class:`RejectedError` when the bounded
        queue is full or the scheduler is draining for shutdown;
        ``ValueError`` for requests that could never be served."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        # written KV footprint: prompt + all but the final emitted token
        if tokens.size + max_new - 1 > self.engine.max_len:
            raise ValueError(
                f"prompt {tokens.size} + max_new {max_new} exceeds "
                f"engine max_len {self.engine.max_len}")
        need = KV.pages_for(tokens.size + max_new - 1, self.page_size)
        if need > self.kv.num_pages:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.kv.num_pages} — it could never be admitted")
        if self._draining or (self._shutdown is not None
                              and getattr(self._shutdown, "requested",
                                          False)):
            raise RejectedError("admission rejected: shutting down",
                                snapshot=self.snapshot())
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            raise RejectedError(
                f"admission rejected: queue full "
                f"({len(self.queue)}/{self.max_queue})",
                snapshot=self.snapshot())
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, tokens, max_new, t_enqueue=self._clock(),
                       ttft_budget_s=ttft_budget_s,
                       total_budget_s=total_budget_s)
        self.queue.append(req)
        self.outcomes[rid] = RequestOutcome(
            rid=rid, state=RequestState.QUEUED, prompt_len=tokens.size)
        self.trace.append(("enqueue", rid))
        return rid

    def cancel(self, rid: int) -> bool:
        """Cooperative cancellation, honored at the next tick boundary:
        a queued request is dropped, a running one is evicted (pages
        freed, slot recycled, partial tokens in its outcome).  Returns
        False if ``rid`` is unknown or already terminal."""
        for req in self.queue:
            if req.rid == rid:
                req.cancel = True
                return True
        for sl in self.slots:
            if sl.request is not None and sl.request.rid == rid:
                sl.request.cancel = True
                return True
        return False

    def snapshot(self) -> dict:
        """Queue/slot/page-pool state — attached to RejectedError and
        SchedulerStallError, and useful for live introspection."""
        return {
            "tick": self._ticks,
            "queue_depth": len(self.queue),
            "max_queue": self.max_queue,
            "queued_rids": [r.rid for r in self.queue],
            "live": {i: {"rid": sl.request.rid,
                         "prefilled": sl.n_prefilled,
                         "emitted": sl.n_emitted,
                         "max_new": sl.request.max_new}
                     for i, sl in enumerate(self.slots)
                     if sl.request is not None},
            "free_pages": self.kv.free_count,
            "num_pages": self.kv.num_pages,
            "cached_pages": self.kv.cached_count,
            "outstanding_growth": self._outstanding_growth(),
            "draining": self._draining,
        }

    # ------------------------------------------------------- page budget
    def _footprint(self, req: _Request) -> int:
        return KV.pages_for(len(req.tokens) + req.max_new - 1,
                            self.page_size)

    def _outstanding_growth(self) -> int:
        """Pages live slots may still demand before they finish."""
        need = 0
        for i, sl in enumerate(self.slots):
            if sl.request is not None:
                need += self._footprint(sl.request) - self.kv.held(i)
        return need

    # --------------------------------------------------- fault isolation
    def _degrade(self, reason: str, err: Exception) -> None:
        self.stats.degraded[reason] = self.stats.degraded.get(reason, 0) + 1
        self.trace.append(("degraded", reason, type(err).__name__))
        _spans.instant("degraded", reason=reason,
                       error=type(err).__name__)

    def _finalize_queued(self, req: _Request, state: RequestState,
                         error: str, error_type: str | None = None) -> None:
        oc = self.outcomes[req.rid]
        oc.state, oc.error, oc.error_type = state, error, error_type
        self.trace.append(("reject", req.rid, state.value))

    def _release_slot(self, i: int, state: RequestState, *,
                      error: str | None = None,
                      error_type: str | None = None) -> None:
        """Terminal transition for the request in slot ``i``: record its
        outcome, keep whatever tokens it emitted for materialization,
        free its pages (refcounted — shared prefix pages survive with
        their other holders), and recycle the slot.  The quarantine
        primitive: DONE and every eviction state go through here so no
        exit path can leak pages."""
        sl = self.slots[i]
        req = sl.request
        if sl.first_tok is not None:
            self._pending.append((req.rid, i, sl.first_tok,
                                  tuple(sl.steps)))
        oc = self.outcomes[req.rid]
        oc.state, oc.emitted = state, sl.n_emitted
        oc.error, oc.error_type = error, error_type
        if state == RequestState.DONE:
            now = self._clock()
            self.stats.requests.append(RequestStats(
                rid=req.rid, prompt_len=len(req.tokens),
                new_tokens=req.max_new,
                queue_wait_s=req.t_admit - req.t_enqueue,
                ttft_s=req.t_first - req.t_enqueue,
                total_s=now - req.t_enqueue,
                decode_tps=req.max_new / max(now - req.t_first, 1e-9),
                emit_s=[t - req.t_enqueue for t in req.emitted_at]))
            self.trace.append(("finish", req.rid, i))
        else:
            self.trace.append(("evict", req.rid, i, state.value))
            _spans.instant("evict", rid=req.rid, slot=i,
                           state=state.value, error=error or "")
        freed = self.kv.free(i)
        self.trace.append(("free", i, tuple(freed)))
        sl.request, sl.first_tok = None, None
        sl.n_prefilled, sl.n_emitted, sl.steps = 0, 0, []

    def _guarded(self, point: str, dispatch, *, rid=None, rids=()):
        """The dispatch degradation ladder: attempt, one retry on the
        engine's own backend, then — for engines advertising
        ``supports_fallback`` — one attempt on the ``xla`` fallback
        backend (bit-exact: all registered backends pass the same
        gate).  The chaos injection point fires host-side before each
        attempt, so injected faults never leave donated buffers
        half-consumed.  Raises the final error; the caller
        quarantines the victims."""
        try:
            faults.maybe_fire(point, rid=rid, rids=rids, attempt=0)
            return dispatch(False)
        except Exception:
            self.stats.dispatch_retries += 1
            try:
                faults.maybe_fire(point, rid=rid, rids=rids, attempt=1)
                return dispatch(False)
            except Exception:
                if not getattr(self.engine, "supports_fallback", False):
                    raise
                self.stats.backend_fallbacks += 1
                faults.maybe_fire(point, rid=rid, rids=rids, attempt=2)
                return dispatch(True)

    def _enforce_deadlines(self) -> None:
        """Tick-boundary enforcement of deadlines, cancellation, and
        shutdown drain — the only places a request leaves the system
        outside DONE/quarantine."""
        if self._shutdown is not None and getattr(self._shutdown,
                                                  "requested", False):
            self._draining = True
        if self._draining:
            while self.queue:
                req = self.queue.popleft()
                self._finalize_queued(req, RequestState.CANCELLED,
                                      "shutdown")
        now = self._clock()
        if self.queue:
            keep: collections.deque[_Request] = collections.deque()
            while self.queue:
                req = self.queue.popleft()
                wait = now - req.t_enqueue
                if req.cancel:
                    self._finalize_queued(req, RequestState.CANCELLED,
                                          "cancelled while queued")
                elif (req.total_budget_s is not None
                        and wait > req.total_budget_s):
                    self._finalize_queued(
                        req, RequestState.TIMED_OUT,
                        f"total budget {req.total_budget_s}s exceeded "
                        f"while queued ({wait:.3f}s)")
                elif (req.ttft_budget_s is not None
                        and wait > req.ttft_budget_s):
                    self._finalize_queued(
                        req, RequestState.TIMED_OUT,
                        f"ttft budget {req.ttft_budget_s}s exceeded "
                        f"while queued ({wait:.3f}s)")
                else:
                    keep.append(req)
            self.queue = keep
        for i, sl in enumerate(self.slots):
            req = sl.request
            if req is None:
                continue
            age = now - req.t_enqueue
            if req.cancel:
                self._release_slot(i, RequestState.CANCELLED,
                                   error="cancelled")
            elif req.total_budget_s is not None \
                    and age > req.total_budget_s:
                self._release_slot(
                    i, RequestState.TIMED_OUT,
                    error=f"total budget {req.total_budget_s}s exceeded "
                          f"({age:.3f}s)")
            elif req.ttft_budget_s is not None and req.t_first is None \
                    and age > req.ttft_budget_s:
                self._release_slot(
                    i, RequestState.TIMED_OUT,
                    error=f"ttft budget {req.ttft_budget_s}s exceeded "
                          f"with no first token ({age:.3f}s)")

    # ------------------------------------------------------------- steps
    def _admit(self):
        for i, sl in enumerate(self.slots):
            if sl.request is not None or not self.queue:
                continue
            req = self.queue[0]
            # deadlock-free reservation: admit only if the request's full
            # footprint fits beside every live slot's remaining growth.
            # A prefix hit covers part of the footprint with shared
            # pages; reclaimable cached-idle pages extend the budget
            # (the allocator evicts them under pressure) except the
            # hit's own pages, which this admission is about to pin.
            need = self._footprint(req)
            hit = None
            avail = self.kv.free_count
            if self.prefix is not None:
                try:
                    hit = self.prefix.lookup(req.tokens)
                except Exception as e:
                    # degraded: budget with the full cold footprint
                    self._degrade("prefix_lookup", e)
                if hit is not None:
                    need -= len(hit.nodes)
                    pinned = hit.pages + (
                        [hit.fork_node.page] if hit.fork_node is not None
                        else [])
                    avail += self.kv.reclaimable_count(exclude=pinned)
            if need + self._outstanding_growth() > avail:
                break                      # FIFO: never skip the head
            self.queue.popleft()
            req.t_admit = self._clock()
            sl.request, sl.first_tok = req, None
            sl.n_prefilled, sl.n_emitted, sl.steps = 0, 0, []
            sl.order = self._admit_seq
            self._admit_seq += 1
            self.outcomes[req.rid].state = RequestState.RUNNING
            hit_tokens = 0
            if self.prefix is not None and hit is not None:
                try:
                    hit_tokens = self.prefix.admit(i, req.tokens, hit=hit)
                except Exception as e:
                    # cold-prefill degradation: drop any partial install
                    # (refcounts make the free safe) and start at 0
                    self._degrade("prefix_admit", e)
                    self.kv.free(i)
                    hit_tokens = 0
                if hit_tokens:
                    # shared pages cover positions [0, hit_tokens);
                    # chunked prefill resumes at the divergent token
                    self.kv.lens[i] = hit_tokens
                    sl.n_prefilled = hit_tokens
            self.trace.append(("admit", req.rid, i))
            if hit_tokens:
                self.trace.append(("prefix_hit", req.rid, i, hit_tokens))
                _spans.instant("prefix_hit", rid=req.rid, slot=i,
                               tokens=hit_tokens)
            if self.check_invariants:
                self.kv.check_no_aliasing()

    def _prefill_step(self) -> bool:
        cands = [(sl.order, i) for i, sl in enumerate(self.slots)
                 if sl.request is not None and not sl.prefill_done]
        if not cands:
            return False
        with _spans.span("prefill_prep"):
            _, i = min(cands)              # earliest admitted first
            sl = self.slots[i]
            req = sl.request
            start = sl.n_prefilled
            # chunk-tail bucketing: the last chunk of a prompt — and the
            # whole divergent remainder after a prefix hit — dispatches
            # at the smallest gemm.bucket_m width that holds it instead
            # of the full admission width, so a 3-token divergent tail
            # does not pay a chunk-wide GEMM.  The width set is the
            # bucket ladder <= chunk, which Engine.warmup_plans
            # pre-resolves.
            rem = len(req.tokens) - start
            width = (self.chunk if rem >= self.chunk
                     else gemm_api.bucket_m(rem))
            end = min(start + width, len(req.tokens))
            final = end == len(req.tokens)
            try:
                self.kv.alloc(i, end)
            except Exception as e:
                # allocator fault (real OOM past the reservation, or
                # injected): quarantine this request only
                self._release_slot(i, RequestState.FAILED,
                                   error=f"page allocation failed: {e}",
                                   error_type=type(e).__name__)
                return True
            chunk = np.zeros((1, width), np.int32)
            chunk[0, :end - start] = req.tokens[start:end]
            # every input is shipped here, once: a retry or a fallback
            # dispatch re-sends the same arrays
            inputs = (self.kv.table_device([i]), self.kv.lens_device([i]),
                      jnp.asarray(chunk),
                      jnp.asarray(end - start - 1, jnp.int32))
            self._step_uploads += len(inputs)

        def dispatch(fb):
            kw = {"fallback": True} if fb else {}
            return self.engine.prefill_chunk(
                self.kv.pages, *inputs, page_size=self.page_size, **kw)

        # tick timing through the obs fenced timer: under sync_per_step
        # the fence closes the clock AFTER the device finishes (real
        # execution time, one host sync — counted); unfenced, the number
        # is honestly a dispatch time (timer.fenced stays False).  The
        # span's ``step=`` attr names the jitted body's GEMM manifest so
        # the trace exporter can attribute per-dispatch GEMM work.
        timer = FencedTimer(fence=self.sync_per_step)
        with _spans.span("prefill_chunk", step=f"prefill_chunk_m{width}",
                         rid=req.rid, slot=i, tokens=end - start,
                         fenced=self.sync_per_step), timer:
            try:
                with _spans.span("dispatch"):
                    tok, pages = self._guarded("prefill_dispatch",
                                               dispatch, rid=req.rid)
            except Exception as e:
                self._release_slot(i, RequestState.FAILED,
                                   error=f"prefill dispatch failed: {e}",
                                   error_type=type(e).__name__)
                return True
            self.kv.pages = pages
            self._count_experts("prefill")
            with _spans.span("fence"):
                timer.fence(tok)
        with _spans.span("prefill_post"):
            self._step_fence_s += timer.fence_s
            self.stats.host_syncs += timer.synced
            dt = timer.elapsed_s
            self.stats.prefill_s += dt
            self.stats.prefill_tick_ms.append(dt * 1e3)
            self.stats.prefill_tokens += end - start
            self.kv.lens[i] = end
            sl.n_prefilled = end
            self.trace.append(("prefill", req.rid, i, start, end))
            if final:
                if self.prefix is not None:
                    # prompt fully prefilled: its full pages are
                    # immutable from here (decode writes land strictly
                    # past the prompt) — index them BEFORE _emit can
                    # free the slot.  An index error only loses future
                    # hits: degrade.
                    try:
                        self.prefix.insert(i, req.tokens)
                    except Exception as e:
                        self._degrade("prefix_insert", e)
                # first token stays on device — it feeds the slot's
                # decode steps through the last-token row, no host sync
                self._last = self._last.at[i].set(tok)
                sl.first_tok = tok
                self._emit(i)
            if self.check_invariants:
                self.kv.check_no_aliasing()
        return True

    def _decode_step(self) -> bool:
        dec = [i for i, sl in enumerate(self.slots) if sl.prefill_done]
        if not dec:
            return False
        with _spans.span("decode_prep"):
            # realized megastep depth: never let a slot over-generate —
            # the shallowest remaining budget among decoding slots caps
            # the drain, so a request finishes exactly at its max_new
            # and the trace/exactly-once invariants hold at every depth
            d = 1
            if self.megastep_depth > 1:
                d = min(self.megastep_depth,
                        min(self.slots[i].request.max_new
                            - self.slots[i].n_emitted for i in dec))
            # per-slot page growth, individually guarded: an allocator
            # fault growing one slot evicts that request only; the rest
            # decode on
            ok = []
            for i in dec:
                try:
                    self.kv.alloc(i, int(self.kv.lens[i]) + d)
                except Exception as e:
                    self._release_slot(i, RequestState.FAILED,
                                       error=f"page allocation failed: {e}",
                                       error_type=type(e).__name__)
                    continue
                ok.append(i)
            if not ok:
                return True                # work happened: quarantines
            mask = np.zeros((self.batch_slots,), bool)
            for i in ok:
                mask[i] = True
            rids = tuple(self.slots[i].request.rid for i in ok)
            inputs = (self.kv.table_device(), self.kv.lens_device(),
                      jnp.asarray(mask))
            # a megastep also ships its tick count (Engine.decode_megastep)
            self._step_uploads += len(inputs) + (d > 1)

        def dispatch(fb):
            kw = {"fallback": True} if fb else {}
            if d > 1:
                last, hist, pages = self.engine.decode_megastep(
                    self.kv.pages, *inputs, self._last, d,
                    page_size=self.page_size,
                    max_depth=self.megastep_depth, **kw)
                return last, [hist[t] for t in range(d)], pages
            last, pages = self.engine.decode_step(
                self.kv.pages, *inputs, self._last,
                page_size=self.page_size, **kw)
            return last, [last], pages

        # same fenced-timer discipline as _prefill_step; ``ticks=d``
        # tells the trace exporter how many decode_step manifests this
        # one dispatch covers (a megastep drain runs d device ticks)
        timer = FencedTimer(fence=self.sync_per_step)
        with _spans.span("decode_tick", step="decode_step", ticks=d,
                         slots=len(ok), fenced=self.sync_per_step), timer:
            try:
                with _spans.span("dispatch"):
                    last, ticks, pages = self._guarded(
                        "decode_dispatch", dispatch, rids=rids)
            except Exception as e:
                # single-victim attribution when the error names a rid
                # (an injected poison request, or any error carrying
                # .rid); otherwise the whole decoding set is poisoned
                bad_rid = getattr(e, "rid", None)
                victims = ([i for i in ok
                            if self.slots[i].request.rid == bad_rid]
                           if bad_rid in rids else ok)
                for i in victims:
                    self._release_slot(i, RequestState.FAILED,
                                       error=f"decode dispatch failed: {e}",
                                       error_type=type(e).__name__)
                return True
            self._last = last
            self.kv.pages = pages
            self._count_experts("decode")
            self.stats.decode_dispatches += 1
            with _spans.span("fence"):
                timer.fence(self._last)
        with _spans.span("decode_post"):
            self._step_fence_s += timer.fence_s
            self.stats.host_syncs += timer.synced
            dt = timer.elapsed_s
            self.stats.decode_s += dt
            self.stats.decode_tick_ms.extend([dt * 1e3 / d] * d)
            for tok_row in ticks:
                step_idx = len(self._history)
                self._history.append(tok_row)
                self.trace.append(("decode", rids))
                for i in ok:
                    self.kv.lens[i] += 1
                    self.slots[i].steps.append(step_idx)
                    self._emit(i)
            if self.check_invariants:
                self.kv.check_no_aliasing()
        return True

    def _count_experts(self, phase: str) -> None:
        rows = getattr(self.engine, "expert_rows", None)
        if rows is not None:
            self.stats.expert_rows.append((phase, rows))

    def _emit(self, i: int):
        sl = self.slots[i]
        req = sl.request
        now = self._clock()
        if req.t_first is None:
            req.t_first = now
        req.emitted_at.append(now)
        sl.n_emitted += 1
        self.stats.decode_tokens += 1
        if sl.n_emitted == req.max_new:
            self._release_slot(i, RequestState.DONE)

    def _materialize(self):
        """Pull the device-side token history to host and assemble each
        request's tokens (one transfer per run, not per step) — full
        outputs for DONE requests, partial tokens into the outcome
        record for evicted ones."""
        hist = (np.stack([np.asarray(h) for h in self._history])
                if self._history else np.zeros((0, self.batch_slots),
                                               np.int32))
        for rid, slot, first, steps in self._pending:
            toks = np.concatenate(
                [[np.asarray(first)], hist[list(steps), slot]]
                if steps else [[np.asarray(first)]]).astype(np.int32)
            oc = self.outcomes.get(rid)
            if oc is not None:
                oc.tokens = toks
            if oc is not None and oc.state == RequestState.DONE:
                self._results[rid] = toks
        self._pending.clear()

    # --------------------------------------------------------------- run
    def step(self) -> bool:
        """One scheduler tick: enforce deadlines/cancellations, admit,
        one prefill chunk, one decode step.  Returns False once no work
        remains.

        Spans (when a tracer is active): ``sched_step`` around the tick,
        with children ``deadlines``, ``admit``, then per phase
        ``<phase>_prep`` (slot choice, page allocation, host inputs and
        their uploads), the dispatch span (``prefill_chunk`` /
        ``decode_tick``, with children ``dispatch`` — the jitted call
        until it returns — and ``fence``) and ``<phase>_post``
        (bookkeeping and token emission)."""
        t0 = time.perf_counter()
        self._ticks += 1
        self._step_fence_s, self._step_uploads = 0.0, 0
        with _spans.span("sched_step", tick=self._ticks):
            # chaos point: delay specs model stragglers (the watchdog
            # must flag them); error specs model scheduler-internal
            # failures (the run()-level try/finally must still release
            # every page)
            faults.maybe_fire("slow_tick", tick=self._ticks)
            with _spans.span("deadlines"):
                self._enforce_deadlines()
            with _spans.span("admit"):
                self._admit()
            did_p = self._prefill_step()
            did_d = self._decode_step()
        wall = time.perf_counter() - t0
        self.stats.steps.append(StepRecord(
            t0, wall * 1e3, self._step_fence_s * 1e3, self._step_uploads))
        if self.watchdog is not None:
            self.watchdog.record(wall)
        return did_p or did_d or bool(self.queue)

    def run(self, requests, max_new_tokens, *,
            ttft_budget_s=None, total_budget_s=None) \
            -> tuple[list[np.ndarray | None], ServeStats]:
        """Serve ``requests`` (list of int32 prompt arrays) to completion.
        ``max_new_tokens``: int, or a per-request sequence; the optional
        deadline budgets broadcast the same way.  Returns (per-request
        generated tokens in submission order — None for requests that
        ended FAILED/CANCELLED/TIMED_OUT, whose structured
        ``RequestOutcome`` in ``stats.outcomes`` carries the reason and
        any partial tokens — and the ServeStats).

        ``max_queue`` is not consulted for this bulk submission (the
        whole batch is enqueued up front); it guards incremental
        ``submit`` callers.  The page-pool leak audit
        (``assert_all_free``) runs on EVERY exit path, including
        exception exits, after live slots are released.
        """
        n = len(requests)
        mn = ([int(max_new_tokens)] * n if np.isscalar(max_new_tokens)
              else [int(m) for m in max_new_tokens])
        if len(mn) != n:
            raise ValueError("max_new_tokens list must match requests")

        def _bcast(v):
            if v is None or np.isscalar(v):
                return [v] * n
            return list(v)
        tbs, wbs = _bcast(ttft_budget_s), _bcast(total_budget_s)
        t0 = time.perf_counter()
        # bulk submission bypasses the incremental-admission guards
        # (bounded queue, shutdown rejection): the caller handed us the
        # whole batch, and a SIGTERM racing this loop must not raise —
        # the first tick's drain cancels the queue with structured
        # outcomes instead
        max_q, self.max_queue = self.max_queue, None
        sd, self._shutdown = self._shutdown, None
        draining, self._draining = self._draining, False
        try:
            rids = [self.submit(r, m, ttft_budget_s=tb, total_budget_s=wb)
                    for r, m, tb, wb in zip(requests, mn, tbs, wbs)]
        finally:
            self.max_queue = max_q
            self._shutdown = sd
            self._draining = draining
        # every tick either prefills a chunk or decodes >=1 token, so this
        # bound is generous; hitting it means a scheduler bug, not load
        max_ticks = 10 + 2 * (sum(mn) + sum(
            -(-len(np.atleast_1d(r)) // self.chunk) for r in requests))
        try:
            for _ in range(max_ticks):
                if not self.step():
                    break
            else:
                raise SchedulerStallError(
                    f"scheduler made no progress in {max_ticks} ticks",
                    snapshot=self.snapshot())
            self._materialize()
            self.stats.host_syncs += 1     # the end-of-run materialize
        except BaseException as e:
            # exception exit: confine the damage — every in-flight
            # request is evicted (pages freed), queued requests are
            # drained to outcomes, partial tokens are salvaged — so the
            # finally-audit below sees a clean pool and the caller sees
            # structured outcomes beside the raised error
            for i, sl in enumerate(self.slots):
                if sl.request is not None:
                    self._release_slot(
                        i, RequestState.FAILED,
                        error=f"run aborted: {e}",
                        error_type=type(e).__name__)
            while self.queue:
                req = self.queue.popleft()
                self._finalize_queued(req, RequestState.CANCELLED,
                                      f"run aborted: {e}",
                                      type(e).__name__)
            try:
                self._materialize()
            except Exception:
                pass                       # salvage only; keep original
            raise
        finally:
            self.stats.wall_s += time.perf_counter() - t0
            if self.watchdog is not None:
                self.stats.stragglers = list(self.watchdog.events)
            if self.prefix is not None:
                self.stats.prefix = self.prefix.snapshot_stats()
            self.stats.trace_dropped = self.trace.dropped
            # view publication: when a metrics registry is active, map
            # this run's ServeStats into it (the dataclass itself is
            # returned unchanged — the registry is a view, not a move)
            if _metrics._ANY:
                _metrics.publish_serve_stats(self.stats)
            # teardown leak audit — success AND error paths: every
            # request freed, so a page refcount still above zero (a
            # free() that dropped a shared reference short) is a leak
            # the free-list count alone cannot see
            self.kv.assert_all_free()
        return [self._results.get(r) for r in rids], self.stats
