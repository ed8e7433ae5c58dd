"""Dispatch flight recorder: a fixed-size ring buffer of GEMM dispatches.

Every ``gemm.execute`` call lands here when a recorder is active: plan
key, (m, n, k), backend, lever (prepack / fine-panel / split-K / quant
format), epilogue, plan-cache hit/miss, wall time, and achieved GFLOPS
with the fraction-of-roofline from ``roofline.analysis.gemm_roofline``.
Scoped exactly like ``gemm.use_backend`` (:func:`use_recorder` /
:func:`no_recorder` / :func:`set_recorder`); when inactive the hook in
``gemm/execute.py`` is a single module-level int check — zero
allocation.

Two dispatch regimes, recorded honestly rather than papered over:

* **Eager** dispatches (operands are concrete arrays — warmup, plan
  probing, direct ``gemm.execute`` use).  Wall time is measurable, but
  only if we fence: JAX dispatches asynchronously, so ``perf_counter``
  around the call measures *dispatch* cost.  A recorder created with
  ``fence=True`` calls ``block_until_ready`` on the result before
  closing the timer — opt-in, because the fence itself changes what you
  measure (it serializes the pipeline).  Unfenced eager records carry
  ``wall_ms`` of the dispatch only and are flagged ``fenced: False``.

* **Traced** dispatches (operands are tracers — every jitted Engine
  step).  Per-call wall time does not exist at trace time and cannot be
  recovered per-GEMM afterwards, so we record the *manifest*: each
  jitted step body opens :func:`manifest_scope`, and traced ``execute``
  calls register their plan (static shape/lever data) under that step
  key, once per compilation.  Scheduler tick spans carry
  ``step=<key>``; at export time ``obs.report`` synthesizes per-GEMM
  child spans under each tick with duration apportioned by the plans'
  ``t_pred`` share, explicitly flagged ``"apportioned": true``.
  Manifests register unconditionally (trace-time cost only), so a
  recorder attached after warmup still sees them.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator

from repro.obs import spans

# Combined hot flag for the execute() hook: nonzero while any recorder
# scope/default is installed OR any manifest scope is open.  The
# inactive fast path in gemm/execute is ``if _HOT: ...`` — one global
# int truth test.
_HOT = 0
_DEFAULT: "FlightRecorder | None" = None
_STATE = threading.local()          # .stack: recorder scopes; .mkey: manifest
_LOCK = threading.Lock()

# step key -> list of manifest records (static plan info registered at
# jit-trace time).  Module-level and persistent: jit traces once per
# shape, so late-attached recorders still see every compiled step.
_MANIFESTS: dict[str, list[dict]] = {}


def active_recorder() -> "FlightRecorder | None":
    stack = getattr(_STATE, "stack", None)
    if stack:
        return stack[-1]
    return _DEFAULT


def set_recorder(rec: "FlightRecorder | None") -> "FlightRecorder | None":
    """Install ``rec`` as the process-default recorder (None uninstalls).
    Returns the previous default."""
    global _DEFAULT, _HOT
    with _LOCK:
        prev = _DEFAULT
        _DEFAULT = rec
        _HOT += (1 if rec is not None else 0) - (1 if prev is not None else 0)
    return prev


@contextlib.contextmanager
def use_recorder(rec: "FlightRecorder | None") -> Iterator["FlightRecorder | None"]:
    """Scope ``rec`` as this thread's active recorder (None = record
    nothing inside, shadowing any process default)."""
    global _HOT
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(rec)
    with _LOCK:
        _HOT += 1
    try:
        yield rec
    finally:
        stack.pop()
        with _LOCK:
            _HOT -= 1


def no_recorder():
    """Scope with recording disabled (shadows any process default)."""
    return use_recorder(None)


@contextlib.contextmanager
def manifest_scope(key: str) -> Iterator[None]:
    """Open around a jitted step *body* (executes at trace time only).

    Traced ``execute`` calls inside register their plan under ``key`` in
    the module-level manifest table.  Entering the scope resets the
    key's record list, so a retrace rewrites rather than duplicates.
    Reentrant traces (a jitted step tracing inside another) stack."""
    global _HOT
    prev = getattr(_STATE, "mkey", None)
    _STATE.mkey = key
    _MANIFESTS[key] = []
    with _LOCK:
        _HOT += 1
    try:
        yield
    finally:
        _STATE.mkey = prev
        with _LOCK:
            _HOT -= 1


def manifests() -> dict[str, list[dict]]:
    """The full step-key -> plan-records manifest table (live view)."""
    return _MANIFESTS


def _plan_record(p, m: int) -> dict:
    """Static (shape/lever) fields shared by ring records and manifests."""
    return {
        "plan": p.describe(),
        "m": int(m), "n": int(p.n), "k": int(p.k),
        "backend": p.backend,
        "lever": p.lever,
        "pack": p.pack,
        "split_k": int(p.split_k),
        "weight_format": p.weight_format,
        "density_bucket": int(p.density_bucket),
        "epilogue": str(p.epilogue) if p.epilogue is not None else "none",
        "decode": bool(p.decode),
        "t_pred": float(p.t_pred),
    }


class FlightRecorder:
    """Fixed-size ring buffer of per-dispatch records.

    ``capacity`` bounds memory; once full, the oldest records are
    overwritten (``wrapped`` counts overwrites).  ``fence=True`` makes
    eager timed entries call ``block_until_ready`` before closing the
    timer — execution time instead of dispatch time, at the cost of
    serializing the pipeline (see docs/observability.md)."""

    def __init__(self, *, capacity: int = 4096, fence: bool = False):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.fence = fence
        self._ring: list[dict | None] = [None] * capacity
        self._idx = 0
        self.total = 0          # dispatches recorded over the lifetime
        self.wrapped = 0        # records overwritten by the ring
        self.traced = 0         # trace-time (manifest) registrations seen
        self._seen: set[str] = set()   # plan keys already dispatched
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    # ---------------------------------------------------------- recording
    def record(self, p, m: int, *, wall_s: float | None,
               fenced: bool) -> None:
        """Record one eager dispatch.  ``wall_s`` is the measured wall
        time (None if timing was impossible); ``fenced`` says whether
        the timer closed behind a ``block_until_ready``."""
        rec = _plan_record(p, m)
        key = rec["plan"]
        rec["ts_ms"] = (time.perf_counter() - self._t0) * 1e3
        if wall_s is not None and wall_s > 0:
            rec["wall_ms"] = wall_s * 1e3
            rec["fenced"] = fenced
            if fenced:
                flops = 2.0 * m * rec["n"] * rec["k"]
                rec["gflops"] = flops / wall_s / 1e9
                rec["roofline_frac"] = _roofline_frac(rec, wall_s)
        with self._lock:
            # plan-cache proxy: first time this recorder sees the key is
            # a miss from the recorder's point of view (the process-wide
            # plan cache may have been warm before we attached — the
            # plan_resolve spans in the trace carry the live resolves).
            rec["plan_cache_hit"] = key in self._seen
            self._seen.add(key)
            if self._ring[self._idx] is not None:
                self.wrapped += 1
            self._ring[self._idx] = rec
            self._idx = (self._idx + 1) % self.capacity
            self.total += 1
        if spans._ANY and wall_s is not None:
            tr = spans.active_tracer()
            if tr is not None and tr.sink == "memory":
                # eager dispatches get real (measured) spans; traced
                # ones get plan-only children at export time.  The
                # profiler sink keeps no events: there the device trace
                # holds each GEMM's real time
                tr._push({"name": "gemm_dispatch", "ph": "X",
                          "ts": tr._now_us() - wall_s * 1e6,
                          "dur": wall_s * 1e6, "pid": 1,
                          "tid": threading.get_ident() % 100_000,
                          "args": rec})

    def note_traced(self) -> None:
        with self._lock:
            self.traced += 1

    # ---------------------------------------------------------- reading
    def dump(self) -> list[dict]:
        """Records in chronological order (oldest surviving first)."""
        with self._lock:
            tail = [r for r in self._ring[self._idx:] if r is not None]
            head = [r for r in self._ring[:self._idx] if r is not None]
            return [dict(r) for r in tail + head]

    def manifests(self) -> dict[str, list[dict]]:
        return _MANIFESTS

    def summary(self) -> dict:
        return {"total": self.total, "wrapped": self.wrapped,
                "traced": self.traced, "capacity": self.capacity,
                "fence": self.fence}


def on_traced(p, m: int, *, stacked: bool = False,
              in_place: bool = False) -> None:
    """Called by ``gemm.execute`` when a dispatch ran on tracers (i.e.
    at jit-trace time).  Registers the plan's static record into the
    open manifest scope, if any — once per compilation, zero
    per-dispatch cost at run time.  ``stacked``: the weight came as a
    stacked pack and a layer index (the layer scan's projections);
    ``in_place``: the backend read that layer's panels where they lie,
    or a grouped run read the whole stack with the layer folded into
    its group axis, with no per-layer slice or copy
    (``stacked_in_place_share``)."""
    mkey = getattr(_STATE, "mkey", None)
    if mkey is not None:
        rec = _plan_record(p, m)
        rec.update(stacked=stacked, in_place=in_place)
        _MANIFESTS.setdefault(mkey, []).append(rec)
    rec = active_recorder()
    if rec is not None:
        rec.note_traced()


def _roofline_frac(rec: dict, wall_s: float) -> float | None:
    """Fraction of the analytic roofline bound achieved by this
    dispatch, against the peaks of the device it ran on; None ("not
    measured") on a device the peaks table does not list, the CPU
    among them (lazy import keeps obs free of repro deps at module
    level)."""
    try:
        from repro.roofline import device_peaks, gemm_roofline
        hw = device_peaks()
        if hw is None:
            return None
        db = rec.get("density_bucket", -1)
        # sparse packs: score against the occupied fraction the layout
        # implies (the bucket's midpoint), not the dense shape's work
        wd = 1.0 if db < 0 else max(0.05, 1.0 - (db + 0.5) / 10.0)
        t_bound = gemm_roofline(rec["m"], rec["n"], rec["k"], hw=hw,
                                weight_format=rec["weight_format"],
                                weight_density=wd)
        if t_bound and t_bound > 0:
            return min(1.0, t_bound / wall_s)
    except Exception:
        pass
    return None


def stacked_in_place_share(records: list[dict]) -> float | None:
    """Of one step manifest's dispatches whose weight came as a stacked
    pack, the share read in place; None when the step has none."""
    stacked = [r for r in records if r.get("stacked")]
    if not stacked:
        return None
    return sum(bool(r.get("in_place")) for r in stacked) / len(stacked)


def reset_manifests() -> None:
    """Test hook: forget every registered manifest (jit caches persist,
    so a cleared manifest only repopulates on a fresh trace)."""
    _MANIFESTS.clear()
