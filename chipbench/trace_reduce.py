"""Reduce a profiler trace to device busy time, per-op and per-module time.

A JAX profiler trace (``.xplane.pb``) of one process on a TPU holds a
plane per device (``/device:TPU:<n>``) whose line ``XLA Ops`` carries
one event per HLO operation run and whose line ``XLA Modules`` carries
one event per program run, and a host plane (``/host:CPU``) whose lines
carry the benchmark's own ``jax.profiler.TraceAnnotation`` spans.  All
share one clock.

The traced window is the harness's ``window`` span.  Busy time is the
union of op intervals inside it; the idle share is what is left.  Each
idle gap is labelled with the harness span the host was in at the gap's
middle, so a gap reads as "the host was in ``step``" (scheduling,
dispatch, the per-step sync) or "in ``generator``" (making and
submitting requests).

Which ops compute the projection GEMMs is decided from the compiled
HLO of each step (``gemm_op_names``), by this rule, written from the
HLO of the paged prefill and decode steps at stablelm-3b's widths on a
v5e: an instruction belongs to the GEMMs when

* its ``op_name`` ends in ``dot_general`` (the projection's dot, or an
  elementwise epilogue XLA fused into it) and does not lie inside the
  attention (``flash_attn``), or
* it reads a packed weight parameter (``params...data``) of the step:
  XLA converts each stacked fp32 weight to bf16 once per dispatch before
  the layer loop, and that pass is part of computing the GEMM, or
* it is a Pallas kernel (``pallas_call``) outside the attention.

Where no op matches, the GEMM time is missing and so is every metric
built on it.
"""
from __future__ import annotations

import dataclasses
import re

# a TPU op event is named by its HLO text: "%name = shape opcode(...)"
_EVENT = re.compile(r"^%?([\w.\-]+) = (.*)$", re.S)
_OPCODE = re.compile(r"[\s)}]([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
# control-flow ops span the ops of their bodies, which the trace also has
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([\w\-]+)\((.*?)\)"
                    r"(?:,|$)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_WEIGHT_PARAM = re.compile(r"%params__[\w.]*___data\.\d+")


@dataclasses.dataclass
class Trace:
    ops: list        # [(name, start_ns, end_ns)] device ops, all devices
    modules: list    # [(name, start_ns, end_ns)] device program runs
    host: list       # [(name, start_ns, end_ns)] harness spans
    t0: int          # traced window, ns
    t1: int
    n_devices: int = 1
    labels: dict = dataclasses.field(default_factory=dict)  # op -> label

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def op_event(text: str) -> tuple:
    """(instruction name, short label) of a device op event's name: the
    instruction as the step's HLO names it, and its opcode and shape."""
    m = _EVENT.match(text)
    if not m:
        return text, text
    name, rest = m.groups()
    op = _OPCODE.search(" " + rest)
    opcode = op.group(1) if op else ""
    shape = _LAYOUT.sub("", rest.split(" ", 1)[0]) if \
        not rest.startswith("(") else "(...)"
    return name, f"{name} {opcode} {shape}".strip()


def from_xplane(path: str, window_span: str = "window",
                host_names=("window", "generator", "step", "stamp",
                            "wait")) -> Trace:
    import jax.profiler
    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host, labels = [], [], [], {}
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name, label = op_event(e.name)
                        labels[name] = label
                        ops.append((name, int(e.start_ns), int(e.end_ns)))
                elif line.name == "XLA Modules":
                    modules.extend((e.name, int(e.start_ns), int(e.end_ns))
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in host_names)
    win = [h for h in host if h[0] == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in {path}")
    t0 = min(h[1] for h in win)
    t1 = max(h[2] for h in win)
    return Trace(ops, modules, host, t0, t1, max(n_dev, 1), labels)


def clip(intervals, lo: int, hi: int) -> list:
    out = []
    for iv in intervals:
        a, b = max(iv[-2], lo), min(iv[-1], hi)
        if b > a:
            out.append((a, b))
    return sorted(out)


def merge(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace) -> float:
    """Union of op intervals inside the window, averaged over devices."""
    return sum(b - a for a, b in merge(clip(tr.ops, tr.t0, tr.t1))) \
        / tr.n_devices


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_ns(tr) / (tr.t1 - tr.t0)


def modules_matching(tr: Trace, key: str) -> list:
    """Program runs whose name contains ``key``, inside the window."""
    return [m for m in tr.modules
            if key in m[0] and m[1] >= tr.t0 and m[2] <= tr.t1]


def op_ns_in(tr: Trace, runs: list, names: set) -> float:
    """Device time of ops named in ``names`` that ran inside ``runs``."""
    runs = sorted((a, b) for _, a, b in runs)
    total = 0.0
    for name, a, b in tr.ops:
        if name not in names:
            continue
        for ra, rb in runs:
            if ra <= a and b <= rb:
                total += b - a
                break
    return total


def top_ops(tr: Trace, n: int = 10) -> list:
    """Ops by their summed device time inside the window (seconds),
    leaving out loops and calls, whose bodies' ops are listed apart."""
    tot: dict = {}
    for name, a, b in tr.ops:
        label = tr.labels.get(name, name)
        if label.split(" ")[1:2] and label.split(" ")[1] in CONTAINERS:
            continue
        a, b = max(a, tr.t0), min(b, tr.t1)
        if b > a:
            tot[label] = tot.get(label, 0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / tr.n_devices] for k, v in ranked]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The ``n`` longest device-idle gaps inside the window, each
    labelled by the innermost harness span covering its middle."""
    busy = merge(clip(tr.ops, tr.t0, tr.t1))
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [h for h in tr.host if h[0] != "window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        cover = [h for h in spans if h[1] <= mid <= h[2]]
        label = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                 else "outside harness spans")
        out.append([label, (b - a) / 1e9])
    return out


def gemm_op_names(hlo_text: str) -> set:
    """Names of the HLO instructions that compute projection GEMMs (the
    rule in the module docstring)."""
    names = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, opcode, operands = m.groups()
        op = _OPNAME.search(line)
        op_name = op.group(1) if op else ""
        if "flash_attn" in op_name:
            continue
        if (op_name.endswith("dot_general")
                or (opcode == "custom-call" and "pallas_call" in op_name)
                or (opcode in ("convert", "fusion", "copy")
                    and _WEIGHT_PARAM.search(operands))):
            names.add(name)
    return names


def module_name(hlo_text: str) -> str:
    m = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""
