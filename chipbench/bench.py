"""One run of one cell: build, warm, ramp, measure, check.

The window drives the scheduler's incremental path as a streaming server
would: ``ContinuousBatchingScheduler.submit`` as requests come due and
``.step`` while there is work, over an ``Engine`` whose GEMM backend,
fusion and packing are the program's defaults.  ``sync_per_step=True``:
a streaming server needs each step's tokens before it can send them.
The harness pins only what an operator sets for a deployment: slots,
``max_len``, page size, prefill chunk, the pool (slots x ``max_len``)
and the offered load.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import adapter, correct, generator, spec, trace_reduce, work
from chipbench import window as W
from chipbench.peaks import PEAKS, peaks_for

DRAIN_LIMIT_S = 60.0     # an open loop waits this long for late firsts
TRACE_S = 3.0            # the profiler covers the window's last seconds
SLOW_STEP_S = 0.25       # a step() this long is reported with its dispatches


class NoChip(RuntimeError):
    """JAX sees no accelerator of a known kind, or too few chips."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: spec.Cell
    sizes: dict
    setup_s: float
    rec: W.Recorder
    stats: object                 # the scheduler's ServeStats
    prefill_ticks: list           # ServeStats.prefill_tick_ms in window
    decode_ticks: list
    full_width: list              # per prefill tick in window: full chunk?
    peaks: dict | None
    chunk: int                    # the prefill step's full width
    trace: trace_reduce.Trace | None = None
    traced: list = dataclasses.field(default_factory=list)  # Dispatches
    gemm_names: dict = dataclasses.field(default_factory=dict)
    modules: dict = dataclasses.field(default_factory=dict)

    def work_of(self, d: W.Dispatch) -> work.Work:
        if d.kind == "prefill":
            return work.prefill_chunk(self.sizes, d.width, d.start, d.end)
        return work.decode_step(self.sizes, self.cell.settings["slots"],
                                list(d.contexts))

    def module_runs(self, kind: str) -> list | None:
        """[(dispatch, program run)] of the traced window's ``kind``
        dispatches, in order, or None when the runs of the step program
        on the device do not pair one to one with the host's dispatches.
        Each step is fenced before the next is dispatched, so the n-th
        run on the device is the n-th dispatch."""
        if self.trace is None or kind not in self.modules:
            return None
        runs = sorted(trace_reduce.modules_matching(self.trace,
                                                    self.modules[kind]),
                      key=lambda m: m[1])
        mine = [d for d in self.traced if d.kind == kind]
        if not mine or len(runs) != len(mine):
            say(f"trace: {len(runs)} {kind} program runs against "
                f"{len(mine)} {kind} dispatches; {kind} device metrics "
                f"left out")
            return None
        return list(zip(mine, runs))


class CompileCounter:
    """Counts lowerings and backend compiles while ``armed``.  One
    listener per process, however many runs the process makes."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    _live: "CompileCounter | None" = None

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        if CompileCounter._live is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._dispatch)
        CompileCounter._live = self

    @staticmethod
    def _dispatch(event, duration, **kw):
        c = CompileCounter._live
        if c is not None and c.armed and event in CompileCounter.EVENTS:
            c.count += 1


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the fixed ``<root>/.jax_cache``
    (the path is part of the cache key), through the program's own
    switch; every compile is kept, so only a checkout's first run of a
    cell compiles.  Called by the entry points before JAX starts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _device(cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"needs a TPU; JAX's first device is {dev.platform}"
                         f" ({dev.device_kind})")
        if len(devs) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips; JAX sees "
                         f"{len(devs)}")
        try:
            peaks_for(dev.device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
    return dev, devs


def build(cell, seed: int, sizes: dict, quant: str | None = None):
    """Weights from the seed on the device, packed at load by the
    program's ``pack_for_inference``, and an Engine at the program's
    defaults.

    Two programs, each compiled once for every seed (the key is an
    argument): the program's own ``init_params``, then the load-time pack
    with the raw tree donated, so raw and packed weights never both sit
    whole in memory.  ``model_zoo.build_packed`` makes the same tree leaf
    by leaf, but its fused leaves (Q/K/V, gate/up) join the random
    generation of their parts in one program, which the TPU compiler
    does not finish compiling (PERF.md, Open questions).  ``quant``
    packs the layer projections in the program's own lower-precision
    format (``pack_for_inference(quant=)``): the precision control that
    ``chipbench/control.py`` runs."""
    from repro.models import model_zoo, transformer
    from repro.runtime.serve_loop import Engine
    import jax
    base = model_zoo.get_config(cell.config["program_arch"])
    cfg = dataclasses.replace(base, name=cell.config_name, **sizes)
    raw = jax.jit(lambda key: transformer.init_params(cfg, key))(
        jax.random.key(seed))
    params = jax.jit(lambda p: model_zoo.pack_for_inference(
        cfg, p, quant=quant), donate_argnums=0)(raw)
    del raw
    jax.block_until_ready(params)
    return Engine(cfg, params, max_len=cell.settings["max_len"], packed=True)


def drive(sched, stream, rec: W.Recorder, st: dict, *, loop_open: bool,
          depth: int, ramp_s: float, seconds: float, trace_s: float | None,
          counter: CompileCounter, ann):
    """Serve ``stream`` through ``sched``: ramp, window, and (open loop)
    the wait for the first tokens of requests due in the window.
    Every ``step()`` slower than ``SLOW_STEP_S`` is kept in
    ``st["slow"]`` with the fenced device times of the dispatches it made
    (``ServeStats``), to tell a stall on the device from one on the host.
    Returns (trace directory or None, the traced dispatches or None)."""
    import jax
    clock = time.perf_counter
    t0 = clock()
    rec.w0, rec.w1 = t0 + ramp_s, t0 + ramp_s + seconds
    nxt = next(stream)
    prompts = st["prompts"]
    st["slow"] = []
    seen = 0
    tick0 = None
    trace_dir = trace_span = win_ann = None
    traced = None
    while True:
        now = clock()
        if tick0 is None and now >= rec.w0:
            counter.armed = True
            tick0 = (len(sched.stats.prefill_tick_ms),
                     len(sched.stats.decode_tick_ms), len(rec.dispatches))
            st["backlog0"] = rec.backlog()
        # the profiler covers the window's last trace_s seconds, so that
        # writing the trace out falls after the window
        if trace_s and trace_dir is None and \
                now >= max(rec.w0, rec.w1 - trace_s):
            trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
            win_ann = ann("window")
            win_ann.__enter__()
            traced = len(rec.dispatches)
        if now >= rec.w1 and st.get("tick1") is None:
            counter.armed = False
            st["tick1"] = (len(sched.stats.prefill_tick_ms),
                           len(sched.stats.decode_tick_ms),
                           len(rec.dispatches))
            st["backlog1"] = rec.backlog()
            if win_ann is not None:
                win_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                trace_span = rec.dispatches[traced:]
            if not loop_open:
                break
        if st.get("tick1") is not None and loop_open:
            due = rec.due_in_window()
            if all(r.first is not None or r.failed for r in due) or \
                    now >= rec.w1 + DRAIN_LIMIT_S:
                break
        with ann("generator"):
            if loop_open:
                while t0 + nxt.due_s <= now:
                    _submit(sched, rec, prompts, nxt, due=t0 + nxt.due_s)
                    nxt = next(stream)
            else:
                for _ in range(depth - rec.backlog()):
                    _submit(sched, rec, prompts, nxt, due=clock())
                    nxt = next(stream)
        if adapter.busy(sched):
            stats = sched.stats
            n_p, n_d = len(stats.prefill_tick_ms), len(stats.decode_tick_ms)
            t_step = clock()
            with ann("step"):
                sched.step()
            t = clock()
            if t - t_step > SLOW_STEP_S:
                st["slow"].append((t_step - rec.w0, t - t_step,
                                   stats.prefill_tick_ms[n_p:],
                                   stats.decode_tick_ms[n_d:]))
            with ann("stamp"):
                events, seen = adapter.events_since(sched, seen)
                rec.observe(events, t)
        else:
            with ann("wait"):
                time.sleep(max(0.0, min(t0 + nxt.due_s - clock(), 0.005))
                           if loop_open else 0.001)
    st["tick0"] = tick0
    return trace_dir, trace_span


def _profile_options():
    """Device ops and the harness's own spans only: no Python function
    tracing, which on a busy host loop writes millions of events."""
    import jax.profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def _submit(sched, rec, prompts, req, *, due):
    rid = sched.submit(req.prompt, req.max_new)
    prompts[rid] = req.prompt
    rec.submitted(rid, due=due, t=time.perf_counter(),
                  prompt_len=len(req.prompt), max_new=req.max_new)


def _read_trace(trace_dir: str) -> trace_reduce.Trace:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return trace_reduce.from_xplane(files[0])


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        sizes: dict | None = None, quant: str | None = None) -> dict:
    """One run of ``cell``.  The benchmark's own runs leave ``quant``
    unset; ``chipbench/control.py`` sets it to serve from the program's
    own lower-precision packs, the precision control."""
    import jax
    from repro import gemm
    from repro.runtime.batching import ContinuousBatchingScheduler

    dev, devs = _device(cell, require_tpu)
    peaks = PEAKS.get(dev.device_kind)
    say(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
        f"cache {jax.config.jax_compilation_cache_dir}")
    counter = CompileCounter()
    s = cell.settings
    sizes = sizes or cell.model_sizes()
    slots, ps, chunk = s["slots"], s["page_size"], s["prefill_chunk"]
    num_pages = slots * (s["max_len"] // ps)

    with jax.default_device(dev):
        t = time.perf_counter()
        eng = build(cell, seed, sizes, quant)
        say(f"load+pack: {time.perf_counter() - t:.2f}s "
            f"({sizes['num_layers']} layers, d_model {sizes['d_model']}, "
            f"{sizes['param_dtype']} weights)")
        t = time.perf_counter()
        eng.warmup_plans(batch_slots=slots, prefill_chunk=chunk,
                         page_size=ps, num_pages=num_pages)
        say(f"warmup: {time.perf_counter() - t:.2f}s")
        hlo = adapter.step_hlo(eng, slots=slots, chunk=chunk, page_size=ps,
                               num_pages=num_pages) if trace else {}
        sched = ContinuousBatchingScheduler(
            eng, batch_slots=slots, prefill_chunk=chunk, page_size=ps,
            num_pages=num_pages, sync_per_step=True)
        mix = cell.traffic
        loop_open = mix["loop"] == "open"
        stream = generator.Stream(mix, vocab=sizes["vocab_size"], seed=seed,
                                rate=s.get("rate"))
        depth = int(mix.get("queue_per_slot", 0) * slots)
        bucket = sched.chunk
        rec = W.Recorder(lambda rem: bucket if rem >= bucket
                         else gemm.bucket_m(rem))
        st = {"prompts": {}}
        ann = jax.profiler.TraceAnnotation
        trace_dir, traced = drive(
            sched, stream, rec, st, loop_open=loop_open, depth=depth,
            ramp_s=float(s["ramp_s"]), seconds=seconds,
            trace_s=TRACE_S if trace else None,
            counter=counter, ann=ann)
        setup_s = rec.w0 - t_start
        mem = dev.memory_stats() or {}
        peak_bytes = int(mem.get("peak_bytes_in_use", 0))

        (p0, d0, _), (p1, d1, _) = st["tick0"], st["tick1"]
        win_prefill = [d for d in rec.dispatches[st["tick0"][2]:
                                                 st["tick1"][2]]
                       if d.kind == "prefill"]
        ctx = Context(
            cell=cell, sizes=sizes, setup_s=setup_s, rec=rec,
            stats=sched.stats,
            prefill_ticks=sched.stats.prefill_tick_ms[p0:p1],
            decode_ticks=sched.stats.decode_tick_ms[d0:d1],
            full_width=[d.width == bucket and d.end - d.start == bucket
                        for d in win_prefill],
            peaks=peaks, chunk=bucket, traced=traced or [],
            gemm_names={k: trace_reduce.gemm_op_names(v)
                        for k, v in hlo.items()},
            modules={k: trace_reduce.module_name(v) for k, v in hlo.items()})
        if trace_dir is not None:
            ctx.trace = _read_trace(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        _report_window(rec, ctx, counter, loop_open, st)

        chosen = cell.per_layer if trace else cell.end_to_end
        metrics = {}
        for m in chosen:
            v = spec.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result = {"attempted": len(_attempted(rec, loop_open)),
                  "failed": sum(1 for r in _attempted(rec, loop_open)
                                if r.failed or (loop_open and
                                                r.first is None)),
                  "metrics": metrics,
                  "backlog": [st["backlog0"], st["backlog1"]],
                  "device": {"platform": dev.platform,
                             "kind": dev.device_kind, "count": len(devs),
                             "memory_peak_bytes": peak_bytes}}
        if ctx.trace is not None:
            result["device"]["busy_s"] = \
                trace_reduce.busy_ns(ctx.trace) / 1e9
            result["device"]["window_s"] = ctx.trace.window_s
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(ctx.trace),
                "idle_gaps": trace_reduce.idle_gaps(ctx.trace)}

        # ---- correctness, after the window, with the program's state freed
        served = adapter.served_tokens(sched)
        prompts = st["prompts"]
        rids = correct.sample(served, prompts, seed, s["check_tokens"])
        adapter.release(sched)
        del sched, eng, ctx
        gc.collect()
        t = time.perf_counter()
        ref = spec.load_reference(cell.config["reference"])
        pad_to = -(-s["max_len"] // 256) * 256     # one length compiles
        stats, n_tok = ({}, 0)
        if rids:
            stats, n_tok = correct.served_gap(ref, sizes, seed, rids, prompts,
                                              served, pad_to)
        say(f"reference: {len(rids)} requests, {n_tok} served tokens "
            f"compared in {time.perf_counter() - t:.2f}s; {stats}")
    checks = {name: {"value": stats.get(name), "limit": limit}
              for name, limit in s["limits"].items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    checks["compared_tokens"] = {"value": n_tok, "limit": s["check_tokens"]}
    checks["failed_requests"] = {"value": result["failed"], "limit": 0}
    result["gaps"] = stats
    ok = ok and n_tok >= s["check_tokens"] and result["failed"] == 0
    if dev.platform != "tpu":
        # no timing, rate or device share is ever reported off a chip
        result["metrics"] = {}
        result.pop("breakdown", None)
    return {"correct": bool(ok), **result, "checks": checks}


def _attempted(rec: W.Recorder, loop_open: bool) -> list:
    """Requests the window is answerable for: an open loop's requests due
    inside it; a closed loop's requests live at any point inside it."""
    if loop_open:
        return rec.due_in_window()
    return [r for r in rec.reqs.values()
            if r.submitted < rec.w1 and (r.done is None or r.done > rec.w0)]


def _report_window(rec, ctx, counter, loop_open, st) -> None:
    say(f"window: {rec.length:.2f}s after {ctx.setup_s:.2f}s of set-up; "
        f"{len(ctx.prefill_ticks)} prefill and {len(ctx.decode_ticks)} "
        f"decode dispatches; compilations inside the window: "
        f"{counter.count}")
    say(f"backlog (submitted, not finished): {st['backlog0']} at the "
        f"window's start, {st['backlog1']} at its end")
    slow = st["slow"]
    say(f"steps over {SLOW_STEP_S * 1e3:.0f} ms: {len(slow)}" + "".join(
        f"; at {at:+.2f}s of the window {wall * 1e3:.0f} ms, fenced "
        f"prefill {sum(p):.0f} ms in {len(p)}, decode {sum(d):.0f} ms in "
        f"{len(d)}" for at, wall, p, d in slow[:10]))
    if loop_open:
        late = [r.submitted - r.due for r in rec.due_in_window()]
        if late:
            say(f"generator lateness over {len(late)} requests due in the "
                f"window: p50 {np.percentile(late, 50) * 1e3:.2f} ms, max "
                f"{max(late) * 1e3:.2f} ms")
