"""Plan-store autotuner: ``python -m repro.launch.autotune --plan-store P``.

Offline measured autotune (core/autotune.measured_autotune) over the
serving plan surface, committed to a persistent plan store that
``launch/serve --plan-store`` (and any Engine built with
``plan_store=``) then starts hot from:

  * the paper's twelve prefill GEMMs at M = PAPER_M, per weight format
    (fp32 and, with ``--quant``, int8 + ternary);
  * the decode ladder: the same shapes at every ``gemm.DECODE_M_BUCKETS``
    width under the decode policy arm (split-K candidates scored);
  * with ``--sparse-buckets``, the sparse-ternary arm: each shape swept
    at the given zero-group-fraction deciles with synthetic group-sparse
    weights, committed under density-bucketed store keys.

Every committed plan passed the bit-exactness gate; every measured win
cleared the retry-on-noise floor (mis-tune guard: a candidate that never
beats the analytic plan by ``NOISE_RTOL`` is NOT deployed — the analytic
plan stands, recorded as ``analytic_kept``).

``--dry-run`` (CI serving-smoke job) sweeps one tiny shape, then proves
the store ROUND-TRIPS: save, reload in a fresh PlanStore, and assert the
tuned plan comes back equal and validated — the contract a warm-started
server relies on.
"""
from __future__ import annotations

import argparse
import json
import time

from repro import gemm as gemm_api
from repro import obs
from repro.core import autotune
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import PAPER_GEMM_SHAPES, PAPER_M


def _sweep_one(m, n, k, *, weight_format, decode, label, args,
               density_bucket=-1):
    t0 = time.perf_counter()
    with obs.span("autotune_sweep", label=label, m=m, n=n, k=k,
                  format=weight_format, decode=decode,
                  density_bucket=density_bucket) as sp:
        mp = autotune.measured_autotune(
            m, n, k, weight_format=weight_format, decode=decode,
            trials=args.trials, max_retries=args.max_retries,
            max_candidates=args.max_candidates,
            density_bucket=density_bucket)
        sp.set(analytic_kept=mp.analytic, speedup=float(mp.speedup),
               candidates=mp.candidates, retries=mp.retries,
               rejected=mp.rejected)
    row = {"label": label, "M": m, "N": n, "K": k,
           "format": weight_format, "decode": decode,
           "density_bucket": density_bucket,
           "sweep_s": round(time.perf_counter() - t0, 3), **mp.row()}
    kind = "analytic kept" if mp.analytic else \
        f"tuned {mp.speedup:.2f}x"
    print(f"  {label:<28s} M={m:<4d} N={n:<5d} K={k:<5d} "
          f"{weight_format:<7s} {'decode' if decode else 'prefill'}: "
          f"{kind} ({mp.candidates} candidates, {mp.retries} retries, "
          f"{mp.rejected} gate-rejected)")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan-store", required=True, metavar="PATH",
                    help="store file to populate (loaded first if it "
                         "exists — re-runs extend, corrupt files are "
                         "discarded with a warning, never a crash)")
    ap.add_argument("--quant", action="store_true",
                    help="also sweep the quantized weight formats "
                         "(int8, ternary) per shape")
    ap.add_argument("--sparse-buckets", default=None, metavar="B,B",
                    help="comma-separated density buckets (0..9) to "
                         "sweep the sparse-ternary arm at, per shape "
                         "(e.g. '3,5,7')")
    ap.add_argument("--decode-buckets", action="store_true",
                    help="also sweep the decode ladder: every "
                         "gemm.DECODE_M_BUCKETS width per shape, under "
                         "the decode policy arm")
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--max-candidates", type=int, default=4)
    ap.add_argument("--out", default=None, metavar="JSON",
                    help="also write the sweep rows (MeasuredPlan.row "
                         "per dispatch) to this JSON file")
    ap.add_argument("--dry-run", action="store_true",
                    help="one tiny shape + store round-trip assert "
                         "(the CI smoke)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the sweep as a Chrome-trace/Perfetto "
                         "timeline (autotune_sweep spans with per-round "
                         "autotune_measure children)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = None
    if args.trace_out:
        tracer = obs.Tracer()
        obs.set_tracer(tracer)

    store = gemm_api.PlanStore.load(args.plan_store)
    if store.invalidated:
        print(f"plan store {args.plan_store} discarded: "
              f"{store.invalidated} — starting fresh")
    elif len(store):
        print(f"plan store {args.plan_store}: extending "
              f"{store.info().entries} existing entries")

    rows = []
    with gemm_api.use_plan_store(store):
        if args.dry_run:
            rows.append(_sweep_one(32, 64, 64, weight_format="fp32",
                                   decode=False, label="dry", args=args))
            # sparse-ternary arm smoke: a density-bucketed key must
            # sweep, commit, and round-trip exactly like a dense one
            rows.append(_sweep_one(32, 128, 512, weight_format="ternary",
                                   decode=False, label="dry-sparse",
                                   args=args, density_bucket=5))
        else:
            formats = ["fp32"] + (["int8", "ternary"] if args.quant
                                  else [])
            for model, op, n, k in PAPER_GEMM_SHAPES:
                for fmt in formats:
                    rows.append(_sweep_one(
                        PAPER_M, n, k, weight_format=fmt, decode=False,
                        label=f"{model}/{op}", args=args))
            if args.sparse_buckets:
                buckets = [int(b) for b in
                           args.sparse_buckets.split(",") if b != ""]
                for model, op, n, k in PAPER_GEMM_SHAPES:
                    for db in buckets:
                        rows.append(_sweep_one(
                            PAPER_M, n, k, weight_format="ternary",
                            decode=False, density_bucket=db,
                            label=f"{model}/{op}@d{db}", args=args))
            if args.decode_buckets:
                for model, op, n, k in PAPER_GEMM_SHAPES:
                    for bucket in gemm_api.DECODE_M_BUCKETS:
                        rows.append(_sweep_one(
                            bucket, n, k, weight_format="fp32",
                            decode=True,
                            label=f"{model}/{op}@m{bucket}", args=args))

    path = store.save()
    info = store.info()
    print(f"plan store saved -> {path}: {info.entries} entries "
          f"({info.autotuned} measured-autotuned)")

    # round-trip proof: a FRESH store (a warm-starting server) reads
    # back every committed plan equal and pre-validated — no analytic
    # re-resolution, no gate re-runs
    fresh = gemm_api.PlanStore.load(path)
    assert not fresh.invalidated, fresh.invalidated
    assert len(fresh) == info.entries, (len(fresh), info.entries)
    for key in store.keys():
        p = fresh.lookup(key)
        assert p is not None, f"round-trip lost {key}"
        assert p == store.lookup(key), f"round-trip changed {key}"
        assert p.validated, f"round-trip entry not validated: {key}"
    print(f"round-trip OK: {len(fresh)} entries reload equal and "
          f"validated from a fresh store")
    if args.dry_run:
        print("dry-run OK: sweep committed a gate-passed plan and the "
              "store round-trips")

    if tracer is not None:
        obs.set_tracer(None)
        tracer.export_chrome_trace(args.trace_out)
        print(f"trace written -> {args.trace_out} "
              f"({len(tracer.events)} span events)")

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"meta": {"store": path,
                                "entries": info.entries,
                                "autotuned": info.autotuned},
                       "rows": rows}, f, indent=1)
        print(f"sweep rows -> {args.out}")
    return rows


if __name__ == "__main__":
    main()
