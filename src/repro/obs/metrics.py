"""Metrics registry: counters, gauges, and deterministic histograms.

One registry unifies the serving stack's four pre-existing stats
surfaces — ``runtime.batching.ServeStats``,
``runtime.prefix_cache.PrefixCacheStats``, ``gemm.plan_store.StoreInfo``
and ``gemm.plan_cache_info`` — as *views*: the dataclass / namedtuple
APIs stay exactly as they were (no caller or test churn); the obs layer
publishes them into the registry (:func:`publish_serve_stats`,
:func:`publish_prefix_stats`) or pulls them at snapshot time via
registered collectors (:func:`gemm_collector` for the plan cache and
plan store).  Exporters: Prometheus text (:meth:`prometheus_text`) and
a JSON-able snapshot (:meth:`snapshot`).

Histograms use *fixed* bucket boundaries chosen at construction — a
seeded serve run produces a bit-identical snapshot (minus explicitly
timing-valued metrics, which are wall-clock and therefore excluded by
the determinism test via the ``_ms``/``_seconds`` naming convention).

Scoping mirrors ``gemm.use_backend``: :func:`use_metrics` /
:func:`set_metrics` with a module-level activity flag so inactive call
sites cost one int check.
"""
from __future__ import annotations

import contextlib
import json
import threading
from typing import Callable, Iterator

_ANY = 0
_DEFAULT: "MetricsRegistry | None" = None
_STATE = threading.local()
_LOCK = threading.Lock()


def active_metrics() -> "MetricsRegistry | None":
    stack = getattr(_STATE, "stack", None)
    if stack:
        return stack[-1]
    return _DEFAULT


def set_metrics(reg: "MetricsRegistry | None") -> "MetricsRegistry | None":
    """Install ``reg`` as the process default (None uninstalls)."""
    global _DEFAULT, _ANY
    with _LOCK:
        prev = _DEFAULT
        _DEFAULT = reg
        _ANY += (1 if reg is not None else 0) - (1 if prev is not None else 0)
    return prev


@contextlib.contextmanager
def use_metrics(reg: "MetricsRegistry | None") -> Iterator["MetricsRegistry | None"]:
    global _ANY
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(reg)
    with _LOCK:
        _ANY += 1
    try:
        yield reg
    finally:
        stack.pop()
        with _LOCK:
            _ANY -= 1


def _labelkey(labels: dict | None) -> tuple:
    return tuple(sorted(labels.items())) if labels else ()


class Counter:
    """Monotonic counter, optionally labelled."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._series: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels):
        key = _labelkey(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        return self._series.get(_labelkey(labels), 0.0)

    def series(self) -> dict[tuple, float]:
        with self._lock:
            return dict(self._series)


class Gauge:
    """Last-write-wins value, optionally labelled."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._series: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels):
        with self._lock:
            self._series[_labelkey(labels)] = float(value)

    def value(self, **labels) -> float:
        return self._series.get(_labelkey(labels), 0.0)

    def series(self) -> dict[tuple, float]:
        with self._lock:
            return dict(self._series)


class Histogram:
    """Fixed-bucket histogram: ``buckets`` are the inclusive upper
    bounds, in increasing order; an implicit +Inf bucket catches the
    rest.  Fixed boundaries (no adaptive resizing) keep snapshots
    deterministic for deterministic inputs."""

    kind = "histogram"

    def __init__(self, name: str, buckets: tuple, help: str = ""):
        if not buckets or list(buckets) != sorted(buckets):
            raise ValueError("buckets must be a non-empty increasing sequence")
        self.name = name
        self.help = help
        self.buckets = tuple(float(b) for b in buckets)
        self._series: dict[tuple, list] = {}   # key -> [counts..., +inf, sum, n]
        self._lock = threading.Lock()

    def observe(self, value: float, **labels):
        key = _labelkey(labels)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = [0] * (len(self.buckets) + 1) + [0.0, 0]
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    s[i] += 1
                    break
            else:
                s[len(self.buckets)] += 1
            s[-2] += float(value)
            s[-1] += 1

    def series(self) -> dict[tuple, list]:
        with self._lock:
            return {k: list(v) for k, v in self._series.items()}


# Default time buckets (ms): span two decades around typical tick times.
TIME_BUCKETS_MS = (0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100,
                   250, 500, 1000, 2500)
# Shape buckets for m (token rows per dispatch).
M_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


class MetricsRegistry:
    """Namespace of instruments plus snapshot-time collectors.

    ``counter``/``gauge``/``histogram`` are get-or-create (idempotent,
    so call sites don't coordinate creation).  ``add_collector``
    registers a callback run at snapshot/export time — used to pull the
    gemm plan-cache and plan-store surfaces, which are process-global
    and cheapest to read on demand."""

    def __init__(self):
        self._instruments: dict[str, object] = {}
        self._collectors: list[Callable[["MetricsRegistry"], None]] = []
        self._lock = threading.Lock()

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help), Counter)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help), Gauge)

    def histogram(self, name: str, buckets: tuple = TIME_BUCKETS_MS,
                  help: str = "") -> Histogram:
        return self._get(name, lambda: Histogram(name, buckets, help),
                         Histogram)

    def _get(self, name, make, cls):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = self._instruments[name] = make()
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {inst.kind}")
            return inst

    def add_collector(self, fn: Callable[["MetricsRegistry"], None]):
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def _collect(self):
        for fn in list(self._collectors):
            fn(self)

    # --------------------------------------------------------- exporters
    def snapshot(self, *, collect: bool = True) -> dict:
        """JSON-able snapshot: sorted metric names, label sets as sorted
        ``k=v`` strings — byte-stable for identical inputs."""
        if collect:
            self._collect()
        out: dict = {}
        with self._lock:
            instruments = dict(self._instruments)
        for name in sorted(instruments):
            inst = instruments[name]
            entry: dict = {"kind": inst.kind}
            if inst.kind == "histogram":
                entry["buckets"] = list(inst.buckets)
                entry["series"] = {
                    _labelstr(k): {"counts": v[:-2], "sum": v[-2],
                                   "count": v[-1]}
                    for k, v in sorted(inst.series().items())}
            else:
                entry["series"] = {_labelstr(k): v for k, v in
                                   sorted(inst.series().items())}
            out[name] = entry
        return out

    def write_snapshot(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=2, sort_keys=True)
        return path

    def prometheus_text(self, *, collect: bool = True) -> str:
        """Prometheus text exposition format (text/plain; version 0.0.4)."""
        if collect:
            self._collect()
        lines = []
        with self._lock:
            instruments = dict(self._instruments)
        for name in sorted(instruments):
            inst = instruments[name]
            if inst.help:
                lines.append(f"# HELP {name} {inst.help}")
            lines.append(f"# TYPE {name} {inst.kind}")
            if inst.kind == "histogram":
                for key, s in sorted(inst.series().items()):
                    cum = 0
                    for ub, c in zip(inst.buckets, s[:-3]):
                        cum += c
                        lines.append(
                            f'{name}_bucket{{{_promlabels(key, le=ub)}}} {cum}')
                    cum += s[len(inst.buckets)]
                    lines.append(
                        f'{name}_bucket{{{_promlabels(key, le="+Inf")}}} {cum}')
                    lines.append(f"{name}_sum{_prombrace(key)} {s[-2]}")
                    lines.append(f"{name}_count{_prombrace(key)} {s[-1]}")
            else:
                for key, v in sorted(inst.series().items()):
                    lines.append(f"{name}{_prombrace(key)} {_fmt(v)}")
        return "\n".join(lines) + "\n"


def _labelstr(key: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in key) or "_"


def _promlabels(key: tuple, **extra) -> str:
    parts = [f'{k}="{v}"' for k, v in key]
    parts += [f'{k}="{v}"' for k, v in extra.items()]
    return ",".join(parts)


def _prombrace(key: tuple) -> str:
    return "{" + _promlabels(key) + "}" if key else ""


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(v)


# ----------------------------------------------------------------- views
# The four pre-existing stats surfaces, expressed over the registry.
# Dataclass/namedtuple APIs are untouched; these functions map them in.

def publish_serve_stats(stats, reg: "MetricsRegistry | None" = None) -> None:
    """Map a ``runtime.batching.ServeStats`` into the registry.  Called
    by the scheduler at the end of ``run()`` when metrics are active.
    Per-tick durations land in ``_ms`` histograms — wall-clock-valued,
    so excluded from the determinism contract by naming convention."""
    reg = reg or active_metrics()
    if reg is None:
        return
    g = reg.gauge
    c = reg.counter
    g("serve_prefill_tokens").set(stats.prefill_tokens)
    g("serve_decode_tokens").set(stats.decode_tokens)
    g("serve_prefill_ticks").set(len(stats.prefill_tick_ms))
    g("serve_decode_ticks").set(stats.decode_ticks)
    g("serve_decode_dispatches").set(stats.decode_dispatches)
    g("serve_host_syncs").set(stats.host_syncs)
    g("serve_megastep_depth").set(stats.megastep_depth)
    g("serve_requests_completed").set(stats.completed)
    g("serve_requests_failed").set(stats.failed)
    g("serve_dispatch_retries").set(stats.dispatch_retries)
    g("serve_backend_fallbacks").set(stats.backend_fallbacks)
    g("serve_stragglers").set(len(stats.stragglers))
    g("serve_trace_dropped").set(getattr(stats, "trace_dropped", 0))
    g("serve_vmem_clamped_plans").set(stats.vmem_clamped_plans)
    outcomes: dict[str, int] = {}
    for oc in stats.outcomes.values():
        outcomes[oc.state.value] = outcomes.get(oc.state.value, 0) + 1
    for state, n in sorted(outcomes.items()):
        c("serve_request_outcomes_total").inc(n, state=state)
    for reason, n in sorted(stats.degraded.items()):
        c("serve_degraded_total").inc(n, reason=reason)
    h_p = reg.histogram("serve_prefill_tick_ms")
    for v in stats.prefill_tick_ms:
        h_p.observe(v)
    h_d = reg.histogram("serve_decode_tick_ms")
    for v in stats.decode_tick_ms:
        h_d.observe(v)
    # per scheduler step: wall time, and the host's part of it (wall
    # minus the fences' wait on the device); uploads are a count
    g("serve_steps").set(len(stats.steps))
    g("serve_uploads").set(stats.uploads)
    h_s = reg.histogram("serve_step_ms")
    h_h = reg.histogram("serve_step_host_ms")
    for r in stats.steps:
        h_s.observe(r.wall_ms)
        h_h.observe(r.wall_ms - r.fence_ms)
    h_i = reg.histogram("serve_itl_ms")
    for req in stats.requests:
        for gap in req.itl_s:
            h_i.observe(gap * 1e3)
    rows = getattr(stats, "expert_rows", None)
    if rows:
        # rows routed to each held expert, summed over layers and
        # dispatches, and the busiest held expert over the mean
        per_expert = stats.expert_rows_of().sum(axis=(0, 1))
        for e, n in enumerate(per_expert):
            c("serve_expert_rows").inc(int(n), expert=str(e))
        mean = float(per_expert.mean())
        g("serve_expert_load_max_over_mean").set(
            float(per_expert.max()) / mean if mean else 0.0)
    if stats.prefix is not None:
        publish_prefix_stats(stats.prefix, reg)


def publish_prefix_stats(stats, reg: "MetricsRegistry | None" = None) -> None:
    """Map a ``runtime.prefix_cache.PrefixCacheStats`` into the registry."""
    reg = reg or active_metrics()
    if reg is None:
        return
    g = reg.gauge
    g("prefix_cache_lookups").set(stats.lookups)
    g("prefix_cache_hits").set(stats.hits)
    g("prefix_cache_misses").set(stats.misses)
    g("prefix_cache_hit_tokens").set(stats.hit_tokens)
    g("prefix_cache_cow_forks").set(stats.cow_forks)
    g("prefix_cache_inserted_pages").set(stats.inserted_pages)
    g("prefix_cache_evicted_pages").set(stats.evicted_pages)
    g("prefix_cache_cached_pages").set(stats.cached_pages)


def gemm_collector(reg: "MetricsRegistry") -> None:
    """Snapshot-time collector for the gemm-layer surfaces: the in-proc
    plan cache (``gemm.plan_cache_info``) and, when a plan store is
    scoped, its ``StoreInfo``.  Lazy imports — obs never imports gemm
    at module level."""
    from repro import gemm
    info = gemm.plan_cache_info()
    g = reg.gauge
    g("plan_cache_hits").set(info.hits)
    g("plan_cache_misses").set(info.misses)
    g("plan_cache_size").set(info.currsize)
    g("plan_cache_maxsize").set(info.maxsize)
    g("plan_vmem_clamped").set(gemm.vmem_clamped_count())
    si = gemm.plan_store_info()
    if si is not None:
        g("plan_store_hits").set(si.hits)
        g("plan_store_misses").set(si.misses)
        g("plan_store_autotuned").set(si.autotuned)
        g("plan_store_entries").set(si.entries)
