"""Trace summarizer: ``python -m repro.launch.trace_report TRACE.json``.

Reduces a trace written by ``launch/serve --trace-out`` (or any
``obs.Tracer.export_chrome_trace`` output) to the paper-style per-shape
GEMM characterization: one row per (m, n, k, weight_format) with the
dispatch count, lever mix, split-K settings, median achieved GFLOPS and
median fraction-of-roofline — the §4 table shape, produced from live
serving traffic instead of a dedicated benchmark run.

``apportioned`` counts dispatches placed under a tick span by the
step's GEMM manifest, with no timing of their own (the jitted serving
path — see docs/observability.md).  The GFLOPS and roofline medians
read measured records only, the fenced eager dispatches
(``--trace-fence``), and print "—" for a shape that has none; a jitted
GEMM's device time is in a profiler trace (``launch/serve
--profile-dir``).

Per step manifest it prints the share of the layer scan's projections
whose weights were read in place from their stacked pack
(``obs.stacked_in_place_share``): 100 % where every one ran a kernel
that reads the layer's panels where they lie (or, for an expert bank,
the whole stack with the layer folded into the group axis), 0 % where
each layer's weight was sliced out first (the ``xla`` backend).

Also prints a span census (event counts and total self time by span
name) with ``--spans``, and writes the table as JSON with ``--json``.
"""
from __future__ import annotations

import argparse
import json

from repro.obs import recorder as _recorder
from repro.obs import report as _report
from repro.obs import spans as _spans


def _span_census(trace: dict) -> list[dict]:
    agg: dict[str, dict] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i") or "name" not in ev:
            continue
        g = agg.setdefault(ev["name"], {"count": 0, "total_ms": 0.0})
        g["count"] += 1
        g["total_ms"] += ev.get("dur", 0.0) / 1e3
    return [{"name": n, **v} for n, v in
            sorted(agg.items(), key=lambda kv: -kv[1]["total_ms"])]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="summarize a --trace-out trace into the per-shape "
                    "GEMM table")
    ap.add_argument("trace", help="Chrome-trace JSON from --trace-out")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the table rows as JSON")
    ap.add_argument("--spans", action="store_true",
                    help="print a span census (count + total ms by name)")
    args = ap.parse_args(argv)

    with open(args.trace) as f:
        trace = json.load(f)
    problems = _spans.validate_chrome_trace(trace)
    if problems:
        print(f"WARNING: trace has {len(problems)} schema problems "
              f"(first: {problems[0]})")

    rows = _report.per_shape_table(trace)
    n_ev = len(trace.get("traceEvents", []))
    fr = trace.get("flightRecorder") or []
    mani = trace.get("gemmManifests") or {}
    print(f"{args.trace}: {n_ev} events, {len(fr)} flight-recorder "
          f"records, {len(mani)} step manifests "
          f"({sum(len(v) for v in mani.values())} manifest plans)")
    print()
    print("per-shape GEMM characterization (medians of fenced eager "
          "records; apportioned = jitted, not timed):")
    print(_report.format_table(rows))
    in_place = {k: _recorder.stacked_in_place_share(v)
                for k, v in sorted(mani.items())}
    if any(v is not None for v in in_place.values()):
        print()
        print("stacked weights read in place, per step:")
        for key, share in in_place.items():
            if share is not None:
                print(f"  {key:<24} {100 * share:6.1f} %")

    if args.spans:
        print()
        print("span census:")
        for r in _span_census(trace):
            print(f"  {r['name']:<24} x{r['count']:<7} "
                  f"{r['total_ms']:10.2f} ms total")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"table": rows, "events": n_ev,
                       "flight_records": len(fr),
                       "manifest_steps": len(mani),
                       "stacked_in_place_share": in_place}, f, indent=1)
        print(f"\ntable rows -> {args.json}")
    return rows


if __name__ == "__main__":
    main()
