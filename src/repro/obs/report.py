"""Flight-recorder reductions: per-GEMM trace spans and the paper-style
per-shape table.

Two jobs, both operating on the exported trace object (the JSON
``launch/serve --trace-out`` writes):

* :func:`synthesize_gemm_events` — per-GEMM child spans for the jitted
  serving path.  Inside jit, ``gemm.execute`` runs at trace time; a
  per-dispatch wall clock does not exist, and pretending otherwise
  would be fabrication.  What IS known exactly: the manifest (which
  plans each compiled step dispatches, registered at trace time) and
  each scheduler tick's measured span.  So for every tick span carrying
  a ``step=<key>`` attribute we emit one child span per manifest plan,
  laid out across the tick in proportion to the plans' ``t_pred`` and
  flagged ``"apportioned": true``.  They carry the plan, lever and
  dispatch-count fields only: a duration split by a model is no
  measurement, so no GFLOPS or roofline share is derived from it (a
  device profile gives each GEMM's real time).  Eager dispatches
  (warmup, direct execute) get real measured spans from the recorder,
  flagged ``apportioned: false``.

* :func:`per_shape_table` — the paper's shape-resolved characterization
  from live traffic: per (m, n, k, format), the dispatch count, lever
  mix, and the median achieved GFLOPS and fraction-of-roofline of the
  shape's fenced eager records (None where it has none).  Surfaced by
  the ``launch/trace_report`` CLI.
"""
from __future__ import annotations

import math


def _share_weights(records: list[dict]) -> list[float]:
    """Relative duration weights for a step's manifest plans: scheduler
    ``t_pred`` when finite, else the flop count — normalized to sum 1."""
    raw = []
    for r in records:
        t = r.get("t_pred")
        if t is None or not math.isfinite(t) or t <= 0:
            t = 2.0 * r["m"] * r["n"] * r["k"]
        raw.append(float(t))
    total = sum(raw)
    if total <= 0:
        return [1.0 / len(raw)] * len(raw)
    return [w / total for w in raw]


def synthesize_gemm_events(trace: dict) -> list[dict]:
    """Apportioned per-GEMM child spans for every tick span that names a
    manifested step (see module docstring).  Returns the new events;
    does not mutate ``trace``."""
    manifests = trace.get("gemmManifests") or {}
    if not manifests:
        return []
    out = []
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        step = (ev.get("args") or {}).get("step")
        recs = manifests.get(step)
        if not recs:
            continue
        shares = _share_weights(recs)
        ts = ev["ts"]
        # megastep drains carry ticks=D: the manifest runs once per
        # device-side tick, so the child sequence repeats D times
        ticks = int((ev.get("args") or {}).get("ticks", 1)) or 1
        dur_per_tick = ev["dur"] / ticks
        for t in range(ticks):
            for r, share in zip(recs, shares):
                d = dur_per_tick * share
                args = dict(r)
                args["apportioned"] = True
                args["step"] = step
                out.append({"name": "gemm_dispatch", "ph": "X", "ts": ts,
                            "dur": d, "pid": 1,
                            "tid": ev.get("tid", 1), "args": args})
                ts += d
    return out


def gemm_events(trace: dict) -> list[dict]:
    """Every per-GEMM dispatch span's args dict — measured (eager) and
    apportioned (jitted) alike."""
    return [ev.get("args", {}) for ev in trace.get("traceEvents", [])
            if ev.get("name") == "gemm_dispatch" and ev.get("ph") == "X"]


def _median(vals: list[float]) -> float | None:
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    n = len(vals)
    mid = n // 2
    return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])


def per_shape_table(trace: dict) -> list[dict]:
    """The paper-style shape-resolved characterization from a trace:
    one row per (m, n, k, weight_format) with dispatch count, lever
    mix, median GFLOPS and median roofline fraction.  The medians read
    measured records only — fenced eager dispatches — and are None for
    a shape that has none.  ``apportioned`` counts how many of the
    shape's dispatches are jitted ones placed by the manifest, with no
    timing of their own (0 = all measured).  ``sparse`` lists the
    density buckets seen for the shape (``dense`` or ``d<bucket>`` —
    the sparse-ternary arm's zero-group-fraction decile)."""
    groups: dict[tuple, dict] = {}
    for a in gemm_events(trace):
        if "m" not in a:
            continue
        key = (a["m"], a["n"], a["k"], a.get("weight_format", "fp32"))
        g = groups.setdefault(key, {"count": 0, "apportioned": 0,
                                    "levers": {}, "gflops": [],
                                    "frac": [], "split_k": set(),
                                    "epilogues": set(), "buckets": set()})
        g["count"] += 1
        if a.get("apportioned"):
            g["apportioned"] += 1
        lv = a.get("lever", "?")
        g["levers"][lv] = g["levers"].get(lv, 0) + 1
        if a.get("fenced") and not a.get("apportioned"):
            if a.get("gflops") is not None:
                g["gflops"].append(a["gflops"])
            if a.get("roofline_frac") is not None:
                g["frac"].append(a["roofline_frac"])
        g["split_k"].add(a.get("split_k", 1))
        g["epilogues"].add(a.get("epilogue", "none"))
        g["buckets"].add(a.get("density_bucket", -1))
    rows = []
    for (m, n, k, fmt), g in sorted(groups.items()):
        lever_mix = ",".join(f"{lv}:{c}" for lv, c in
                             sorted(g["levers"].items(),
                                    key=lambda kv: -kv[1]))
        rows.append({
            "m": m, "n": n, "k": k, "format": fmt,
            "dispatches": g["count"],
            "apportioned": g["apportioned"],
            "lever_mix": lever_mix,
            "split_k": ",".join(str(s) for s in sorted(g["split_k"])),
            "sparse": ",".join("dense" if b < 0 else f"d{b}"
                               for b in sorted(g["buckets"])),
            "median_gflops": _median(g["gflops"]),
            "median_roofline_frac": _median(g["frac"]),
        })
    return rows


def format_table(rows: list[dict]) -> str:
    """Fixed-width text rendering of :func:`per_shape_table` rows."""
    if not rows:
        return "(no GEMM dispatch spans in trace)"
    cols = [("m", 6), ("n", 6), ("k", 6), ("format", 8),
            ("dispatches", 10), ("apportioned", 11), ("lever_mix", 26),
            ("split_k", 7), ("sparse", 8), ("median_gflops", 13),
            ("median_roofline_frac", 20)]
    lines = ["  ".join(name.rjust(w) for name, w in cols),
             "  ".join("-" * w for _, w in cols)]
    for r in rows:
        cells = []
        for name, w in cols:
            v = r[name]
            if v is None:
                v = "—"                      # not measured
            elif isinstance(v, float):
                v = f"{v:.3f}"
            cells.append(str(v).rjust(w))
        lines.append("  ".join(cells))
    return "\n".join(lines)
