"""The program's own spans in a profiler trace, and what they attribute:
the device's idle time to host phases, and device time to the KV path.

With the span tracer's profiler sink installed
(``repro.obs.Tracer(sink="profiler")``), the scheduler's spans
(``sched_step`` and its phases, docs/observability.md) land on the host
plane of the JAX profiler trace, on the clock of the device's ``XLA
Ops`` line.  The rule that picks them, and ignores the runtime's own host
events (``PjitFunction``, ``PjRt...Execute``, ...): a host event is a
program span when it carries the metadata key ``span_id``, which the
sink gives every span and which no runtime event carries.

Spans nest by time on the one thread that serves.  A moment of the
window is labelled by the chain of spans covering it, harness and
program alike, outermost first and the harness's ``window`` left out:
``step/sched_step/decode_tick/fence`` is the fence of a decode dispatch
inside the harness's ``step``.

The KV path's device ops are picked from the compiled HLO of a step, as
``trace_reduce.gemm_op_names`` picks the GEMMs, by this rule: an
instruction belongs to the KV path when its ``op_name`` has a
``kv_write`` or ``kv_view`` scope (``runtime/kv_cache.paged_update``,
``paged_gather``, the per-layer pool slice and write-back in
``models/transformer.py``, the key-position mask and the pad of the view
to the attention block), or it is a ``copy`` or ``bitcast`` with no
``op_name`` of such an instruction: the copies XLA inserts to change a
layout carry no metadata of their own.
"""
from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce

SPAN_KEY = "span_id"
OUTSIDE = "outside harness spans"
_KV_SCOPE = re.compile(r"(?:^|/)kv_(?:write|view)(?:/|$)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_LAYOUT_OPS = ("copy", "bitcast")


def read(path: str) -> list:
    """[(name, start_ns, end_ns)] of the program's spans in the profile
    at ``path`` (the rule in the module docstring)."""
    import jax.profiler
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if any(k == SPAN_KEY for k, _ in e.stats):
                    out.append((e.name, int(e.start_ns), int(e.end_ns)))
    return out


def segments(spans: list) -> list:
    """[(start, end, chain)]: the stretches of time over which the same
    spans cover the clock, in order, with their chain (module
    docstring).  Stretches no span covers are left out."""
    bounds = sorted({x for _, a, b in spans for x in (a, b)})
    starts = sorted(spans, key=lambda s: s[1])
    ends = sorted(spans, key=lambda s: s[2])
    active: list = []
    i = j = 0
    out = []
    for p, q in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][1] <= p:
            active.append(starts[i])
            i += 1
        while j < len(ends) and ends[j][2] <= p:
            active.remove(ends[j])
            j += 1
        if active:
            chain = "/".join(n for n, _, _ in
                             sorted(active, key=lambda s: (s[1], -s[2])))
            out.append((p, q, chain))
    return out


def _all_spans(tr: trace_reduce.Trace, spans: list) -> list:
    return [h for h in tr.host if h[0] != "window"] + list(spans)


def idle_intervals(tr: trace_reduce.Trace) -> list:
    """The window's stretches with no op on the device, in order."""
    busy = trace_reduce.merge(trace_reduce.clip(tr.ops, tr.t0, tr.t1))
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(tr: trace_reduce.Trace, spans: list) -> list:
    """[[chain, idle seconds]], most first: every idle nanosecond of the
    window summed under the chain of spans that covers it (harness and
    program spans alike); idle time no span covers reads ``OUTSIDE``."""
    segs = segments(_all_spans(tr, spans))
    tot: dict = {}
    k = 0
    for a, b in idle_intervals(tr):
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        covered = 0
        m = k
        while m < len(segs) and segs[m][0] < b:
            lo, hi = max(a, segs[m][0]), min(b, segs[m][1])
            if hi > lo:
                tot[segs[m][2]] = tot.get(segs[m][2], 0) + (hi - lo)
                covered += hi - lo
            m += 1
        if b - a > covered:
            tot[OUTSIDE] = tot.get(OUTSIDE, 0) + (b - a - covered)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[k, v / 1e9] for k, v in ranked]


def chain_at(segs: list, t: float) -> str:
    i = bisect.bisect_right([s[0] for s in segs], t) - 1
    if i >= 0 and segs[i][0] <= t <= segs[i][1]:
        return segs[i][2]
    return OUTSIDE


def idle_gaps(tr: trace_reduce.Trace, spans: list, n: int = 10) -> list:
    """The ``n`` longest idle gaps, each labelled by the chain of spans
    covering its middle."""
    segs = segments(_all_spans(tr, spans))
    gaps = sorted(idle_intervals(tr), key=lambda g: g[0] - g[1])[:n]
    return [[chain_at(segs, (a + b) / 2), (b - a) / 1e9] for a, b in gaps]


def step_host_ms(spans: list, t0: int, t1: int) -> float | None:
    """Mean over the ``sched_step`` spans wholly inside [t0, t1] of the
    span's duration less the time its ``fence`` spans cover: the host
    time per scheduler step not spent waiting on the device."""
    fences = sorted((a, b) for name, a, b in spans if name == "fence")
    host = []
    for name, a, b in spans:
        if name != "sched_step" or a < t0 or b > t1:
            continue
        inner = trace_reduce.merge(trace_reduce.clip(
            [f for f in fences if a <= f[0] and f[1] <= b], a, b))
        host.append((b - a) - sum(y - x for x, y in inner))
    return sum(host) / len(host) / 1e6 if host else None


def mean_ms(spans: list) -> dict:
    """{span name: mean duration in ms}, longest first."""
    tot: dict = {}
    for name, a, b in spans:
        n, t = tot.get(name, (0, 0))
        tot[name] = (n + 1, t + (b - a))
    return dict(sorted(((k, t / n / 1e6) for k, (n, t) in tot.items()),
                       key=lambda kv: -kv[1]))


def kv_op_names(hlo_text: str) -> set:
    """Names of the HLO instructions of the KV path (the rule in the
    module docstring)."""
    names: set = set()
    for line in hlo_text.splitlines():
        m = trace_reduce._INSTR.match(line)
        if not m:
            continue
        name, opcode, operands = m.groups()
        if opcode in trace_reduce.CONTAINERS:
            continue
        op = trace_reduce._OPNAME.search(line)
        if op is not None:
            if _KV_SCOPE.search(op.group(1)):
                names.add(name)
        elif opcode in _LAYOUT_OPS:
            src = _OPERAND.search(operands)
            if src is not None and src.group(1) in names:
                names.add(name)
    return names


def kv_share(ctx, names: set) -> float | None:
    """Device time of the ops in ``names`` over the device time of the
    traced full-width prefill step runs, in %: the runs are paired with
    their dispatches as ``metrics/_device.gemm_roofline`` pairs them."""
    pairs = ctx.module_runs("prefill")
    if not pairs or not names:
        return None
    runs = [r for d, r in pairs if d.width == ctx.chunk]
    run_ns = sum(b - a for _, a, b in runs)
    if run_ns <= 0:
        return None
    return 100.0 * trace_reduce.op_ns_in(ctx.trace, runs, names) / run_ns
