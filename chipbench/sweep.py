"""Find an open-loop cell's knee: the highest offered rate at which the
backlog does not grow across the window.

    python3 chipbench/sweep.py --workload <cell> --rates 2,3,4 \\
        --seed <n> --seconds <s>

Runs the cell once per rate in one process (set-up compiles once) and
prints one JSON line per rate: the backlog (requests submitted and not
finished) at the window's start and end, the output rate, the TTFT and
gap tails, and ``correct``.  A cell's ``rate`` is then fixed at about
four fifths of the knee; the benchmark's own runs never search.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import bench, spec
    bench.enable_compile_cache(ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = spec.cell(args.workload)
        cell.settings["rate"] = rate
        try:
            r = bench.run(cell, args.seed, args.seconds, False,
                          t_start=time.perf_counter())
        except bench.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "rate": rate,
                          "backlog": r["backlog"], "metrics": r["metrics"],
                          "attempted": r["attempted"],
                          "failed": r["failed"], "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
