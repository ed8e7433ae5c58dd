"""Span tracing: nestable timed scopes exported as a Chrome/Perfetto trace.

A :class:`Tracer` collects *span events* — named, attributed, nestable
timed scopes — from every layer of the stack (plan resolution, weight
packing, autotune sweeps, prefill/decode ticks, megasteps, prefix-cache
operations, fault and degradation events) into one timeline that
``export_chrome_trace`` writes as Chrome-trace JSON, loadable directly
in ``ui.perfetto.dev``.

Activation mirrors ``gemm.use_backend``: a thread-local scope stack over
a process default (:func:`use_tracer` / :func:`set_tracer` /
:func:`no_tracer`), with a module-level activity counter so the
path with tracing off is a single integer check.

Two sinks, one span API.  The default ``sink="memory"`` keeps events in
``Tracer.events`` on ``time.perf_counter``.  ``sink="profiler"``
forwards each span to ``jax.profiler.TraceAnnotation`` instead (entered
at begin, exited at end, the span's attributes as its metadata) and
keeps nothing in memory: inside a ``jax.profiler`` trace the spans land
on the profiler's host plane, on the same clock as the device's ``XLA
Ops`` and ``XLA Modules`` lines, so an idle gap on the device can be
read against what the host was doing.  Every profiler-sink annotation
carries the metadata key ``span_id`` (unique per tracer), which the
runtime's own host events never carry: that key is how a trace reader
tells the program's spans from the runtime's.

Async-dispatch caveat (docs/observability.md): a span around a jitted
call measures *dispatch* unless the caller fences.  Spans themselves
never fence — the scheduler's tick timer (obs/timing) owns the fence
decision, because fencing changes what you measure.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import Any, Iterator

# Module-level activity counter: incremented per active scope entry and
# per process-default install.  The instrumented fast path is
# ``if _ANY: ...`` — one global int truth test when tracing is off.
_ANY = 0
_DEFAULT: "Tracer | None" = None
_STATE = threading.local()          # per-thread tracer override stack
_LOCK = threading.Lock()


def active_tracer() -> "Tracer | None":
    """The innermost scoped tracer, else the process default, else None.
    Call sites should guard with ``if spans._ANY`` first (or use
    :func:`span`, which does)."""
    stack = getattr(_STATE, "stack", None)
    if stack:
        return stack[-1]
    return _DEFAULT


def set_tracer(tracer: "Tracer | None") -> "Tracer | None":
    """Install ``tracer`` as the process default (None uninstalls).
    Returns the previous default."""
    global _DEFAULT, _ANY
    with _LOCK:
        prev = _DEFAULT
        _DEFAULT = tracer
        _ANY += (1 if tracer is not None else 0) - \
                (1 if prev is not None else 0)
    return prev


@contextlib.contextmanager
def use_tracer(tracer: "Tracer | None") -> Iterator["Tracer | None"]:
    """Scope ``tracer`` as this thread's active tracer (None = trace
    nothing inside, even if a process default is installed)."""
    global _ANY
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(tracer)
    with _LOCK:
        _ANY += 1
    try:
        yield tracer
    finally:
        stack.pop()
        with _LOCK:
            _ANY -= 1


def no_tracer():
    """Scope with tracing disabled (shadows any process default)."""
    return use_tracer(None)


class _SpanHandle:
    """Live handle yielded by :func:`span`: ``set(k=v)`` attaches
    attributes that are only known once the work ran."""
    __slots__ = ("tracer", "name", "t0", "args", "sid", "tid", "ann")

    def __init__(self, tracer, name, t0, args, sid, tid):
        self.tracer = tracer
        self.name = name
        self.t0 = t0
        self.args = args
        self.sid = sid
        self.tid = tid
        self.ann = None            # the open TraceAnnotation (profiler sink)

    def set(self, **kw):
        self.args.update(kw)
        if self.ann is not None:
            self.ann.set_metadata(**kw)


class _NoopHandle:
    __slots__ = ()

    def set(self, **kw):
        pass


_NOOP = _NoopHandle()


SINKS = ("memory", "profiler")


class Tracer:
    """Collects span/instant events; thread-safe appends; exported via
    :func:`export_chrome_trace` (or :meth:`chrome_trace` for the dict).

    ``max_events`` bounds memory on long serves: beyond it the OLDEST
    events are dropped (``dropped`` counts them) — the exported window
    is the most recent activity, matching the flight-recorder
    discipline.

    ``sink="profiler"`` forwards every span and instant to
    ``jax.profiler.TraceAnnotation`` and appends nothing to ``events``
    (module docstring); outside a profiler trace the annotations cost a
    no-op ``TraceMe`` each."""

    def __init__(self, *, max_events: int = 200_000, sink: str = "memory"):
        if sink not in SINKS:
            raise ValueError(f"sink={sink!r}: expected one of {SINKS}")
        self.sink = sink
        self._annotation = None
        if sink == "profiler":
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self.t0 = time.perf_counter()
        self.events: list[dict] = []
        self.max_events = max_events
        self.dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)

    # ------------------------------------------------------------ events
    def _now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    def _push(self, ev: dict):
        with self._lock:
            self.events.append(ev)
            if len(self.events) > self.max_events:
                # drop the oldest half in one slice (amortized O(1))
                cut = self.max_events // 2
                self.dropped += cut
                del self.events[:cut]

    def instant(self, name: str, **args):
        """A zero-duration marker event (faults, degradations, evictions);
        a zero-length annotation under the profiler sink."""
        if self._annotation is not None:
            with self._annotation(name, span_id=next(self._ids), **args):
                pass
            return
        self._push({"name": name, "ph": "i", "s": "t",
                    "ts": self._now_us(), "pid": 1,
                    "tid": threading.get_ident() % 100_000,
                    "args": args})

    def begin(self, name: str, **args) -> _SpanHandle:
        tid = threading.get_ident() % 100_000
        h = _SpanHandle(self, name, self._now_us(), dict(args),
                        next(self._ids), tid)
        if self._annotation is not None:
            h.ann = self._annotation(name, span_id=h.sid, **args)
            h.ann.__enter__()
        stack = getattr(_STATE, "spans", None)
        if stack is None:
            stack = _STATE.spans = []
        stack.append(h)
        return h

    def end(self, h: _SpanHandle):
        stack = getattr(_STATE, "spans", None)
        if stack and stack[-1] is h:
            stack.pop()
        if h.ann is not None:
            h.ann.__exit__(None, None, None)
            return
        self._push({"name": h.name, "ph": "X", "ts": h.t0,
                    "dur": self._now_us() - h.t0, "pid": 1, "tid": h.tid,
                    "args": h.args, "id": h.sid})

    # --------------------------------------------------------- exporting
    def chrome_trace(self, *, recorder=None) -> dict:
        """The Chrome-trace JSON object (``traceEvents`` plus the
        flight-recorder dump when ``recorder`` is given).  GEMM dispatch
        spans are synthesized for recorder entries — see
        :func:`repro.obs.report.synthesize_gemm_events`."""
        with self._lock:
            events = list(self.events)
        events.insert(0, {"name": "process_name", "ph": "M", "pid": 1,
                          "args": {"name": "repro serve"}})
        out = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"dropped_events": self.dropped}}
        if recorder is not None:
            from repro.obs import report as _report
            records = recorder.dump()
            out["flightRecorder"] = records
            out["gemmManifests"] = {
                key: list(recs)
                for key, recs in recorder.manifests().items()}
            out["traceEvents"].extend(
                _report.synthesize_gemm_events(out))
        return out

    def export_chrome_trace(self, path: str, *, recorder=None) -> str:
        """Write the Perfetto-loadable trace JSON to ``path``."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(recorder=recorder), f)
        return path


def current_span() -> "_SpanHandle | None":
    """The innermost open span on this thread (recorder entries use it
    to attach themselves to the tick that dispatched them)."""
    stack = getattr(_STATE, "spans", None)
    return stack[-1] if stack else None


class _SpanCM:
    """Re-usable span context manager (plain class, not a generator, so
    the inactive path allocates only this tiny object)."""
    __slots__ = ("name", "kw", "handle")

    def __init__(self, name: str, kw: dict):
        self.name = name
        self.kw = kw
        self.handle = _NOOP

    def __enter__(self):
        if _ANY:
            tr = active_tracer()
            if tr is not None:
                self.handle = tr.begin(self.name, **self.kw)
        return self.handle

    def __exit__(self, *exc):
        h = self.handle
        if h is not _NOOP:
            h.tracer.end(h)
        return False


def span(name: str, **attrs: Any) -> _SpanCM:
    """``with obs.span("prefill_chunk", rid=3):`` — a nestable timed
    scope.  No-op (no event, no tracer lookup beyond one int check)
    when no tracer is active."""
    return _SpanCM(name, attrs)


def instant(name: str, **attrs: Any) -> None:
    """Fire-and-forget marker event (no-op when tracing is off)."""
    if _ANY:
        tr = active_tracer()
        if tr is not None:
            tr.instant(name, **attrs)


def validate_chrome_trace(obj: dict) -> list[str]:
    """Schema check for an exported trace: returns a list of problems
    (empty = valid).  Used by tests and the CI traced-serve step."""
    problems = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["missing traceEvents key"]
    evs = obj["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i} not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "B", "E"):
            problems.append(f"event {i}: bad ph {ph!r}")
            continue
        if "name" not in ev:
            problems.append(f"event {i}: missing name")
        if ph in ("X", "i") and not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i}: missing/bad ts")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            problems.append(f"event {i}: complete event missing dur")
        args = ev.get("args", {})
        if not isinstance(args, dict):
            problems.append(f"event {i}: args not an object")
        else:
            try:
                json.dumps(args)
            except TypeError:
                problems.append(f"event {i}: args not JSON-serializable")
    return problems
