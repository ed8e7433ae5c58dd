"""Where the host's time goes in a cell's traced window: the cell served
with the program's spans on the device trace's clock.

    python3 chipbench/spantrace.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--trace-seconds <s>] [--paired] [--out <file.jsonl>]

A tool beside the benchmark, as ``sweep.py`` and ``control.py`` are; the
benchmark's own runs never use it.  One process on one chip: builds and
warms the cell once, then serves it once per seed (a fresh scheduler,
ramp and window each time) with the profiler on the window's last
``--trace-seconds`` (by default ``bench.TRACE_S``, as a ``--trace 1`` run
has it; longer to catch a rare stall), and the span
tracer's profiler sink (``repro.obs.Tracer(sink="profiler")``) installed
over the traced window beside the harness's ``window`` annotation.
``--paired`` serves each seed a second time without the sink, to measure
what it costs.  One JSON line per run on standard output, and in
``--out`` when given: the cell's per-layer metrics as the benchmark reads
them; ``kv_path_share.prefill``, the device time of the KV path's ops
(``program_spans.kv_op_names``) over that of the full-width prefill step
runs, in %; ``step_host_ms`` from the spans and each span's mean
duration; the steps and prompt tokens
per second of the traced window; the window's idle time by span chain
and its longest idle gaps so labelled; the device's top ops, each
marked ``kv``, ``gemm`` or ``-`` (``-`` includes every op of the decode
step, whose HLO is not searched); and the window's steps over
``bench.SLOW_STEP_S`` with their fenced dispatch times.  Reports no
device metric off a chip.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _WindowWithSink:
    """The harness's ``window`` annotation, with the profiler-sink tracer
    installed as the process default while it is open."""

    def __init__(self, annotation, tracer):
        self.annotation = annotation
        self.tracer = tracer

    def __enter__(self):
        from repro import obs
        self.annotation.__enter__()
        obs.set_tracer(self.tracer)
        return self

    def __exit__(self, *exc):
        from repro import obs
        obs.set_tracer(None)
        return self.annotation.__exit__(*exc)


def serve_once(cell, eng, sizes, seed, seconds, *, sink, hlo, peaks,
               trace_s):
    """One ramp and window of ``cell`` on ``eng``; returns the line."""
    import jax
    from repro import gemm, obs
    from repro.runtime.batching import ContinuousBatchingScheduler
    from chipbench import (adapter, bench, generator, program_spans, spec,
                           trace_reduce)
    from chipbench import window as W

    s = cell.settings
    slots, ps = s["slots"], s["page_size"]
    sched = ContinuousBatchingScheduler(
        eng, batch_slots=slots, prefill_chunk=s["prefill_chunk"],
        page_size=ps, num_pages=slots * (s["max_len"] // ps),
        sync_per_step=True)
    mix = cell.traffic
    loop_open = mix["loop"] == "open"
    stream = generator.Stream(mix, vocab=sizes["vocab_size"], seed=seed,
                              rate=s.get("rate"))
    bucket = sched.chunk
    rec = W.Recorder(lambda rem: bucket if rem >= bucket
                     else gemm.bucket_m(rem))
    st = {"prompts": {}}
    tracer = obs.Tracer(sink="profiler") if sink else None

    def ann(name):
        a = jax.profiler.TraceAnnotation(name)
        return _WindowWithSink(a, tracer) if (name == "window"
                                              and tracer) else a

    counter = bench.CompileCounter()
    trace_dir, traced = bench.drive(
        sched, stream, rec, st, loop_open=loop_open,
        depth=int(mix.get("queue_per_slot", 0) * slots),
        ramp_s=float(s["ramp_s"]), seconds=seconds,
        trace_s=trace_s, counter=counter, ann=ann)
    (p0, d0, i0), (p1, d1, i1) = st["tick0"], st["tick1"]
    ctx = bench.Context(
        cell=cell, sizes=sizes, setup_s=0.0, rec=rec, stats=sched.stats,
        prefill_ticks=sched.stats.prefill_tick_ms[p0:p1],
        decode_ticks=sched.stats.decode_tick_ms[d0:d1],
        full_width=[d.width == bucket and d.end - d.start == bucket
                    for d in rec.dispatches[i0:i1] if d.kind == "prefill"],
        peaks=peaks, chunk=bucket, traced=traced or [],
        gemm_names={k: trace_reduce.gemm_op_names(v)
                    for k, v in hlo.items()},
        modules={k: trace_reduce.module_name(v) for k, v in hlo.items()})
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    ctx.trace = trace_reduce.from_xplane(path)
    spans = program_spans.read(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tr = ctx.trace
    lo, hi = max(rec.w0, rec.w1 - trace_s), rec.w1
    kv = program_spans.kv_op_names(hlo["prefill"])
    gemm_ops = ctx.gemm_names.get("prefill", set())
    top = [[label, sec, "kv" if label.split(" ")[0] in kv else
            "gemm" if label.split(" ")[0] in gemm_ops else "-"]
           for label, sec in trace_reduce.top_ops(tr, 12)]
    line = {
        "workload": cell.name, "seed": seed, "sink": sink,
        "metrics": {m["name"]: spec.load_reader(m["name"])(ctx)
                    for m in cell.per_layer},
        "kv_path_share.prefill": program_spans.kv_share(ctx, kv),
        "kv_ops": len(kv), "gemm_ops": len(gemm_ops),
        "step_host_ms_spans": program_spans.step_host_ms(spans, tr.t0,
                                                         tr.t1),
        "steps_per_s": sum(1 for r in sched.stats.steps
                           if lo <= r.t0 < hi) / (hi - lo),
        "prompt_tok_s_traced": sum(n for t, n in rec.prefill_tokens
                                   if lo <= t < hi) / (hi - lo),
        "compiles_in_window": counter.count,
        "window_s": tr.window_s,
        "busy_s": trace_reduce.busy_ns(tr) / 1e9,
        "program_spans": len(spans),
        "span_mean_ms": program_spans.mean_ms(spans),
        "idle_by_span": program_spans.idle_by_span(tr, spans),
        "idle_gaps": program_spans.idle_gaps(tr, spans),
        "top_ops": top,
        "slow_steps": st["slow"],
    }
    adapter.release(sched)
    del sched, ctx
    gc.collect()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--paired", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import bench, spec
    bench.enable_compile_cache(ROOT)
    cell = spec.cell(args.workload)
    try:
        lines = run(cell, [int(x) for x in args.seeds.split(",") if x],
                    args.seconds, paired=args.paired,
                    trace_s=args.trace_seconds)
        for line in lines:
            text = json.dumps(line)
            print(text, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    return 0


def run(cell, seeds, seconds, *, paired=False, trace_s=None,
        require_tpu=True, sizes=None):
    """Build and warm ``cell`` once, then serve it once per seed (twice
    with ``paired``: with the sink, then without), profiling the last
    ``trace_s`` seconds of each window (``bench.TRACE_S`` when None);
    yields the lines."""
    import jax
    from chipbench import adapter, bench
    from chipbench.peaks import PEAKS
    dev, _ = bench._device(cell, require_tpu)
    s = cell.settings
    sizes = sizes or cell.model_sizes()
    slots, ps, chunk = s["slots"], s["page_size"], s["prefill_chunk"]
    num_pages = slots * (s["max_len"] // ps)
    with jax.default_device(dev):
        eng = bench.build(cell, min(seeds), sizes)
        eng.warmup_plans(batch_slots=slots, prefill_chunk=chunk,
                         page_size=ps, num_pages=num_pages)
        hlo = adapter.step_hlo(eng, slots=slots, chunk=chunk,
                               page_size=ps, num_pages=num_pages)
        for seed in seeds:
            for sink in ((True, False) if paired else (True,)):
                t = time.perf_counter()
                line = serve_once(cell, eng, sizes, seed, seconds,
                                  sink=sink, hlo=hlo,
                                  peaks=PEAKS.get(dev.device_kind),
                                  trace_s=trace_s or bench.TRACE_S)
                line["run_s"] = time.perf_counter() - t
                if dev.platform != "tpu":
                    # no time, rate or device share is reported off a chip
                    for key in ("metrics", "kv_path_share.prefill",
                                "step_host_ms_spans", "steps_per_s",
                                "prompt_tok_s_traced", "busy_s",
                                "idle_by_span", "idle_gaps", "top_ops",
                                "slow_steps", "span_mean_ms", "run_s"):
                        line.pop(key, None)
                yield line


if __name__ == "__main__":
    sys.exit(main())
