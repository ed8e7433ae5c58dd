"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the packed-weight engine (paper deployment) against the per-call and
raw-XLA baselines on the same prompts, reporting prefill/decode
tokens-per-second — the framework-native form of the paper's llama.cpp
integration (§4.7).

With ``--requests N`` it also serves a mixed-length request stream
through the continuous-batching pool (``--batch-slots`` slots, chunked
prefill admission of ``--prefill-chunk`` rows) and reports per-request
latency percentiles: queue wait, time-to-first-token, and per-request
decode tokens/s — the stats fields docs/serving.md describes.

By default the model runs at ``model_zoo.reduced_config`` widths (two
layers, ``d_model`` 64: a CPU smoke size).  ``--full-width`` serves the
configuration's published widths and depth instead, on weights built
straight into the packed layout (``model_zoo.build_packed``) so that
only the packed tree ever sits in device memory.

Observability (docs/observability.md): ``--trace-out PATH`` records the
whole run — pack, plan resolution, warmup, every scheduler tick, prefix
cache and fault events, plus the GEMM flight recorder — as a
Chrome-trace JSON loadable at ui.perfetto.dev and summarizable with
``repro.launch.trace_report``; ``--metrics-out PATH`` writes the unified
metrics registry's snapshot (JSON, plus Prometheus text at PATH.prom).
``--profile-dir DIR`` instead runs the JAX profiler over the whole run
with the span tracer's profiler sink: the scheduler's spans land on the
profiler's host plane, on the device trace's clock, and the profile
(``DIR/plugins/profile/<time>/*.xplane.pb``) loads in TensorBoard or
Perfetto, or reads with ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import gemm as gemm_api
from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo
from repro.runtime.serve_loop import Engine


def _profile_options():
    """Device ops and the program's spans; no Python function tracing,
    which on a busy serving loop writes millions of events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _pct(stats, field):
    return (stats.percentile(field, 50) * 1e3,
            stats.percentile(field, 95) * 1e3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=model_zoo.list_archs())
    ap.add_argument("--full-width", action="store_true",
                    help="serve the configuration's published widths and "
                         "depth (default: the reduced smoke config)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--backend", default=None,
                    choices=gemm_api.list_backends(),
                    help="GEMM backend for this engine's plans "
                         "(default: the process default, xla; the panel "
                         "kernel runs with --backend pallas)")
    ap.add_argument("--no-fusion", action="store_true",
                    help="disable horizontal QKV/gate-up fusion and the "
                         "fused epilogues (A/B escape hatch; default: "
                         "fusion on)")
    ap.add_argument("--quant", default=None, choices=["int8", "ternary"],
                    help="serve on quantized packed weights (mixed "
                         "precision: LM head + embeddings stay fp32); "
                         "every pack is tolerance-gated by the error "
                         "ledger (docs/quantization.md)")
    ap.add_argument("--compare-percall", action="store_true",
                    help="also time the unpacked (per-call) engine")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N mixed-length requests through the "
                         "continuous-batching pool and report "
                         "per-request percentiles")
    ap.add_argument("--batch-slots", type=int, default=4,
                    help="slot-pool width for continuous batching")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill admission width (rows); padded "
                         "to a gemm.bucket_m bucket")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size (tokens); must divide --max-len")
    ap.add_argument("--megastep-depth", type=int, default=1,
                    help="decode ticks fused per host dispatch (the "
                         "decode megastep; 1 = per-tick dispatch)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix cache: requests sharing "
                         "a cached prompt prefix reuse its KV pages "
                         "(refcounted, COW-forked at the divergence "
                         "page) and prefill only the divergent tail")
    ap.add_argument("--plan-store", default=None, metavar="PATH",
                    help="persistent plan/autotune store (JSON): loaded "
                         "corruption-tolerantly at startup (a populated "
                         "store makes the engine start hot — zero "
                         "analytic re-resolution and zero bit-exactness "
                         "gate runs; measured-autotuned winners adopted), "
                         "updated with this run's plans, saved at exit. "
                         "Pre-populate with repro.launch.autotune")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-populate the plan cache and compile the "
                         "serving steps (prefill + decode buckets) "
                         "before the first request")
    ap.add_argument("--watchdog-factor", type=float, default=0.0,
                    help="arm the straggler watchdog over scheduler "
                         "ticks: a tick slower than FACTOR x the EMA is "
                         "flagged and reported (0 = off)")
    ap.add_argument("--ttft-budget-s", type=float, default=None,
                    help="per-request time-to-first-token deadline "
                         "(seconds); requests that miss it end "
                         "TIMED_OUT instead of occupying a slot")
    ap.add_argument("--total-budget-s", type=float, default=None,
                    help="per-request total wall-clock deadline "
                         "(seconds, enqueue-relative)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON timeline of "
                         "this run (span tracing + GEMM flight recorder; "
                         "load at ui.perfetto.dev, or summarize with "
                         "repro.launch.trace_report)")
    ap.add_argument("--trace-fence", action="store_true",
                    help="fence (block_until_ready) eagerly-dispatched "
                         "GEMMs so their recorder entries carry real "
                         "execution times and GFLOPS — serializes the "
                         "pipeline (docs/observability.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot at exit: JSON to PATH "
                         "and Prometheus text beside it (PATH + '.prom')")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="run the JAX profiler over the run, with the "
                         "program's spans on the device trace's clock "
                         "(span tracer's profiler sink); writes the "
                         "profile under DIR")
    args = ap.parse_args()
    if args.profile_dir and args.trace_out:
        ap.error("--profile-dir and --trace-out are two sinks of one "
                 "span tracer; choose one")
    if args.full_width and args.compare_percall:
        ap.error("--compare-percall builds a second, unpacked copy of the "
                 "weights; at --full-width that copy does not fit beside "
                 "the packed one")
    if args.full_width and args.quant:
        ap.error("--quant measures every quantized pack against its raw "
                 "fp32 weight, which a --full-width build never holds")
    enable_compile_cache()

    # obs activation happens BEFORE engine construction so pack /
    # plan-resolve / warmup spans and the jitted steps' GEMM manifests
    # land in the same timeline as the serve itself
    tracer = rec = reg = None
    if args.trace_out:
        tracer = obs.Tracer()
        obs.set_tracer(tracer)
        rec = obs.FlightRecorder(fence=args.trace_fence)
        obs.set_recorder(rec)
    if args.metrics_out:
        reg = obs.MetricsRegistry()
        reg.add_collector(obs.gemm_collector)
        obs.set_metrics(reg)
    if args.profile_dir:
        tracer = obs.Tracer(sink="profiler")
        obs.set_tracer(tracer)
        jax.profiler.start_trace(args.profile_dir,
                                 profiler_options=_profile_options())

    cfg = model_zoo.get_config(args.arch)
    if not args.full_width:
        cfg = model_zoo.reduced_config(cfg)
    mesh = make_host_mesh()
    # the store is loaded first: a --full-width build packs at load and
    # resolves its pack blocks under it
    store = (gemm_api.PlanStore.load(args.plan_store)
             if args.plan_store else None)
    if store is not None:
        info = store.info()
        print(f"plan store {args.plan_store}: {info.entries} entries "
              f"loaded ({info.autotuned} measured-autotuned)"
              + (f"  [invalidated: {store.invalidated}]"
                 if store.invalidated else ""))
    t0 = time.perf_counter()
    params = (model_zoo.build_packed(cfg, fuse=not args.no_fusion,
                                     plan_store=store)
              if args.full_width else model_zoo.build(cfg))
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {cfg.param_dtype} params "
          f"({'published widths' if args.full_width else 'reduced'}); "
          f"weights built in {time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
    if cfg.modality != "text":
        prompts = jnp.asarray(rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)), cfg.cdtype)

    t0 = time.perf_counter()
    eng = Engine(cfg, params, mesh=mesh, max_len=args.max_len, packed=True,
                 backend=args.backend, fuse=not args.no_fusion,
                 quant=args.quant, plan_store=store)
    print(f"model load + pack (untimed in per-call metrics): "
          f"{time.perf_counter() - t0:.2f}s  "
          f"[fusion {'off' if args.no_fusion else 'on'}, "
          f"quant {args.quant or 'off'}]")
    if args.quant:
        from repro.quant import ledger
        ents = ledger.entries()
        if ents:
            worst = max(ents, key=lambda e: e.max_rel / e.tol)
            print(f"error ledger: {len(ents)} packs measured, all within "
                  f"tolerance; worst max_rel {worst.max_rel:.2e} "
                  f"(tol {worst.tol:.0e}, shape {worst.k}x{worst.n})")
            n_sparse = sum(1 for e in ents if e.sparse)
            if n_sparse:
                dens = [e.density for e in ents]
                print(f"  sparse-ternary: {n_sparse}/{len(ents)} packs on "
                      f"the compressed zero-group layout, mean occupied "
                      f"density {sum(dens) / len(dens):.2f}")
    if cfg.modality != "text":
        logits, _ = eng.prefill(prompts)
        print(f"stub-frontend arch: prefill ok, logits {logits.shape}")
        return
    if args.warmup:
        t0 = time.perf_counter()
        wt = eng.warmup_plans(batch_slots=args.batch_slots,
                              prefill_chunk=args.prefill_chunk,
                              page_size=args.page_size,
                              megastep_depth=args.megastep_depth)
        pc = wt.pop("plan_cache")
        ps = wt.pop("plan_store", None)
        n_bucket = wt.pop("decode_bucket_plans")
        steps = ", ".join(f"{k} {v * 1e3:.0f}ms" for k, v in wt.items())
        print(f"warmup ({time.perf_counter() - t0:.2f}s): {steps}; "
              f"{n_bucket} decode-bucket plans pre-resolved, "
              f"{pc.currsize} plans cached — first serving tick pays "
              f"no jit/plan latency")
        if ps is not None:
            print(f"  plan store: {ps.hits} hits / {ps.misses} misses "
                  f"({ps.autotuned} autotuned entries adopted)")
    gen, stats = eng.generate(prompts, args.max_new)
    qd = (f", density {stats.quant_density:.2f} "
          f"({stats.quant_sparse_packs} sparse packs)"
          if stats.quant_density is not None else "")
    print(f"packed engine (fused={stats.fused}, quant={stats.quant}{qd}): "
          f"prefill {stats.prefill_tps:,.0f} tok/s, "
          f"decode {stats.decode_tps:,.0f} tok/s")
    print(f"  plan cache: {stats.plan_cache.hits} hits / "
          f"{stats.plan_cache.misses} misses "
          f"({stats.plan_cache.currsize} cached, "
          f"{stats.vmem_clamped_plans} vmem-clamped)"
          if stats.plan_cache else "")
    if stats.plan_store is not None:
        sp = stats.plan_store
        print(f"  plan store: {sp.hits} hits / {sp.misses} misses "
              f"({sp.autotuned} autotuned, {sp.entries} entries)")
    if args.compare_percall:
        eng2 = Engine(cfg, params, mesh=mesh, max_len=args.max_len,
                      packed=False, backend=args.backend)
        gen2, stats2 = eng2.generate(prompts, args.max_new)
        print(f"per-call engine: prefill {stats2.prefill_tps:,.0f} tok/s, "
              f"decode {stats2.decode_tps:,.0f} tok/s")
        print("outputs identical:", bool(jnp.array_equal(gen, gen2)))

    if args.requests > 0:
        if args.prefix_cache:
            # shared-preamble traffic (the workload the cache exists
            # for): 80% of requests open with one fixed preamble of
            # half the prompt budget, then a unique tail
            pre = rng.integers(0, cfg.vocab_size,
                               max(args.prompt_len // 2, 1)) \
                .astype(np.int32)
            tail_hi = max(args.prompt_len - pre.size, 4)
            reqs = [np.concatenate(
                        [pre, rng.integers(0, cfg.vocab_size,
                                           rng.integers(1, tail_hi + 1))
                         .astype(np.int32)])
                    if rng.random() < 0.8 else
                    rng.integers(0, cfg.vocab_size,
                                 rng.integers(4, args.prompt_len + 1))
                    .astype(np.int32)
                    for _ in range(args.requests)]
        else:
            reqs = [rng.integers(0, cfg.vocab_size,
                                 rng.integers(4, args.prompt_len + 1))
                    .astype(np.int32) for _ in range(args.requests)]
        mns = [int(m) for m in
               rng.integers(2, args.max_new + 1, args.requests)]
        # graceful drain: SIGTERM finishes in-flight requests, cancels
        # the queue with structured outcomes, and still saves the plan
        # store below — the grace-window exit docs/serving.md describes
        from repro.runtime.fault_tolerance import GracefulShutdown
        gs = GracefulShutdown().install()
        try:
            outs, sstats = eng.serve(
                reqs, batch_slots=args.batch_slots, max_new_tokens=mns,
                prefill_chunk=args.prefill_chunk,
                page_size=args.page_size,
                megastep_depth=args.megastep_depth,
                prefix_cache=args.prefix_cache,
                watchdog_factor=args.watchdog_factor or None,
                shutdown=gs, ttft_budget_s=args.ttft_budget_s,
                total_budget_s=args.total_budget_s,
                sync_per_step=True)  # exact TTFT / queue-wait pctiles
        finally:
            gs.uninstall()
        qw = _pct(sstats, "queue_wait_s")
        tf = _pct(sstats, "ttft_s")
        print(f"continuous batching ({args.requests} requests, "
              f"{args.batch_slots} slots, chunk {args.prefill_chunk}, "
              f"megastep D={args.megastep_depth}, prefix cache "
              f"{'on' if args.prefix_cache else 'off'}):")
        print(f"  aggregate: {sstats.total_tps:,.0f} generated tok/s "
              f"({sstats.decode_tokens} tokens in {sstats.wall_s:.2f}s)")
        print(f"  queue wait  p50 {qw[0]:8.1f} ms   p95 {qw[1]:8.1f} ms")
        print(f"  TTFT        p50 {tf[0]:8.1f} ms   p95 {tf[1]:8.1f} ms")
        print(f"  per-request decode tok/s: "
              f"p50 {sstats.percentile('decode_tps', 50):,.0f}   "
              f"p5 {sstats.percentile('decode_tps', 5):,.0f}")
        print(f"  per-phase ticks: prefill "
              f"p50 {sstats.phase_percentile('prefill', 50):6.1f} ms / "
              f"p99 {sstats.phase_percentile('prefill', 99):6.1f} ms   "
              f"decode p50 {sstats.phase_percentile('decode', 50):6.1f} "
              f"ms / p99 {sstats.phase_percentile('decode', 99):6.1f} ms")
        print(f"  decode dispatch collapse: {sstats.decode_ticks} ticks "
              f"in {sstats.decode_dispatches} dispatches "
              f"({sstats.host_syncs} host syncs)")
        import collections as _coll
        by_state = _coll.Counter(o.state.value
                                 for o in sstats.outcomes.values())
        extras = ", ".join(f"{k} {v}" for k, v in sorted(by_state.items())
                           if k != "DONE")
        print(f"  outcomes: {by_state.get('DONE', 0)}/{args.requests} "
              f"DONE" + (f" ({extras})" if extras else ""))
        if gs.requested:
            print("  graceful shutdown: drained in-flight requests, "
                  "cancelled the queue")
        # the degradation ladder re-dispatches a failed step on the xla
        # backend: nonzero counters mean part of the run did not run on
        # the requested backend
        print(f"  degradation ladder: {sstats.backend_fallbacks} backend "
              f"fallbacks, {sstats.dispatch_retries} dispatch retries, "
              f"degraded {dict(sorted(sstats.degraded.items())) or 0}")
        if args.watchdog_factor:
            print(f"  watchdog (factor {args.watchdog_factor:g}): "
                  f"{len(sstats.stragglers)} straggler ticks"
                  + ("".join(f"\n    tick {ev.step}: {ev.dt * 1e3:.1f} ms "
                             f"(EMA {ev.ema * 1e3:.1f} ms)"
                             for ev in sstats.stragglers[:5])))
        if sstats.prefix is not None:
            px = sstats.prefix
            print(f"  prefix cache: {px.hits}/{px.lookups} hits "
                  f"({px.hit_rate:.0%}), {px.hit_tokens} prompt tokens "
                  f"reused, {px.cow_forks} COW forks, "
                  f"{px.evicted_pages} pages evicted, "
                  f"{px.cached_pages} pages cached at end")

    mem = jax.devices()[0].memory_stats()
    if mem and "peak_bytes_in_use" in mem:
        print(f"device peak memory: {mem['peak_bytes_in_use'] / 2**30:.2f} "
              f"GiB ({jax.devices()[0].device_kind})")

    if store is not None:
        store.save()
        print(f"plan store saved -> {store.path} "
              f"({store.info().entries} entries)")

    if args.profile_dir:
        jax.profiler.stop_trace()
        obs.set_tracer(None)
        print(f"profile written under {args.profile_dir} (device ops and "
              f"the program's spans on one clock)")
    elif tracer is not None:
        obs.set_tracer(None)
        obs.set_recorder(None)
        tracer.export_chrome_trace(args.trace_out, recorder=rec)
        s = rec.summary()
        print(f"trace written -> {args.trace_out} "
              f"({len(tracer.events)} span events"
              + (f", {tracer.dropped} dropped" if tracer.dropped else "")
              + f"; flight recorder: {s['total']} eager dispatches, "
              f"{s['traced']} traced registrations, "
              f"fence {'on' if s['fence'] else 'off'})")
        print("  load at ui.perfetto.dev, or summarize: "
              f"python -m repro.launch.trace_report {args.trace_out}")
    if reg is not None:
        obs.set_metrics(None)
        reg.write_snapshot(args.metrics_out)
        with open(args.metrics_out + ".prom", "w") as f:
            f.write(reg.prometheus_text(collect=False))
        print(f"metrics snapshot -> {args.metrics_out} "
              f"(+ {args.metrics_out}.prom, Prometheus text)")


if __name__ == "__main__":
    main()
