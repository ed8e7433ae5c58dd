"""Chip benchmark of the serving path: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout (it puts ``src`` and the root on the
path itself).  Builds the cell's configuration from the seed on the
chip, warms exactly the cell's step shapes, ramps the cell's traffic to
a steady state, measures for ``--seconds``, then checks the served
tokens against a plain reference.  Human-readable lines go to standard
error, ending with each compared number beside its limit; the last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` with ``--trace 1``,
and ``checks``).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the last
seconds of the window.  Exits nonzero, printing no result, without a
TPU of a known kind or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import bench, spec
    bench.enable_compile_cache(ROOT)
    cell = spec.cell(args.workload)
    try:
        result = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
