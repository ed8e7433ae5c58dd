"""Readings that set the limits of the correctness comparison.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds <s> [--out <file.jsonl>]

Runs the cell in one process (set-up compiles once): as the benchmark
does on each of ``--seeds``, and on each of ``--control-seeds`` served
from the program's own int8 packs (``pack_for_inference(quant="int8")``),
the precision below the configuration's, whose served tokens go through
the same comparison.  A limit lies between the largest reading of the
program and the smallest of the control (``PERF.md`` gives both).  One
JSON line per run on standard output, and in ``--out`` when given.  The
benchmark's own runs never do this.
"""
from __future__ import annotations

import time

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import bench, spec
    bench.enable_compile_cache(ROOT)
    cell = spec.cell(args.workload)
    runs = [(s, None) for s in _seeds(args.seeds)] + \
        [(s, "int8") for s in _seeds(args.control_seeds)]
    for seed, quant in runs:
        try:
            r = bench.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter(), quant=quant)
        except bench.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        line = json.dumps({
            "workload": args.workload, "seed": seed,
            "served": "int8 packs" if quant else "program",
            "correct": r["correct"], "gaps": r["gaps"],
            "checks": r["checks"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
