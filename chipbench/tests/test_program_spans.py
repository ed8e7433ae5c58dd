"""The program's spans in a trace: idle attribution, host time per step,
and the KV path's ops picked from a step's compiled HLO."""
import contextlib
import glob
import os
import time
import types

import jax
import pytest

from chipbench import adapter, bench, program_spans as PS, spec
from chipbench import trace_reduce as TR
from chipbench import window as W
from chipbench.tests import _tiny
from chipbench.tests.test_trace_reduce import _trace as harness_only

MS = 1_000_000


def _trace():
    # window 0..100 ms; device busy 10-30 and 50-60 ms
    ops = [("fusion.1", 10 * MS, 30 * MS), ("fusion.2", 50 * MS, 60 * MS)]
    host = [("window", 0, 100 * MS), ("step", 2 * MS, 39 * MS),
            ("stamp", 39 * MS, 45 * MS), ("step", 46 * MS, 100 * MS)]
    return TR.Trace(ops, [], host, 0, 100 * MS)


# program spans of two steps (ms): prep, dispatch, fence, post
SPANS = [(n, a * MS, b * MS) for n, a, b in [
    ("sched_step", 3, 38),
    ("prefill_prep", 4, 8), ("prefill_chunk", 8, 32),
    ("dispatch", 8, 10), ("fence", 10, 32), ("prefill_post", 32, 37),
    ("sched_step", 47, 99),
    ("decode_prep", 47, 49), ("decode_tick", 49, 90),
    ("dispatch", 49, 50), ("fence", 50, 90), ("decode_post", 90, 99),
    ("prefix_hit", 5, 5),                         # an instant
]]


def test_idle_time_is_summed_under_the_spans_covering_it():
    got = dict(PS.idle_by_span(_trace(), SPANS))
    ms = 1e-3
    assert got == pytest.approx({
        PS.OUTSIDE: 3 * ms,                          # 0-2, 45-46
        "step": 4 * ms,                              # 2-3, 38-39, 46-47,
                                                     # 99-100
        "step/sched_step": 2 * ms,                   # 3-4, 37-38
        "step/sched_step/prefill_prep": 4 * ms,
        "step/sched_step/prefill_chunk/dispatch": 2 * ms,
        "step/sched_step/prefill_chunk/fence": 2 * ms,   # 30-32
        "step/sched_step/prefill_post": 5 * ms,
        "step/sched_step/decode_prep": 2 * ms,
        "step/sched_step/decode_tick/dispatch": 1 * ms,
        "step/sched_step/decode_tick/fence": 30 * ms,    # 60-90
        "step/sched_step/decode_post": 9 * ms,
        "stamp": 6 * ms,
    })
    assert sum(got.values()) == pytest.approx(TR.idle_share(_trace()) * 0.1)


def test_idle_gaps_are_labelled_by_the_chain_of_spans():
    gaps = PS.idle_gaps(_trace(), SPANS, n=3)
    assert gaps == [
        ["step/sched_step/decode_tick/fence", pytest.approx(0.040)],  # 60-100
        ["stamp", pytest.approx(0.020)],                               # 30-50
        ["step/sched_step/prefill_prep", pytest.approx(0.010)]]        # 0-10


def test_harness_spans_alone_still_read_step():
    tr = harness_only()
    assert PS.idle_gaps(tr, [])[0] == ["step", pytest.approx(0.035)]
    assert TR.idle_gaps(tr)[0] == ["step", pytest.approx(0.035)]
    by = dict(PS.idle_by_span(tr, []))
    assert set(by) <= {"step", "generator", PS.OUTSIDE}
    assert sum(by.values()) == pytest.approx(0.063)


def test_mean_span_durations():
    got = PS.mean_ms(SPANS)
    assert got["sched_step"] == pytest.approx((35 + 52) / 2)
    assert got["fence"] == pytest.approx((22 + 40) / 2)
    assert got["prefix_hit"] == 0.0
    assert list(got)[0] == "sched_step"                # longest first


def test_step_host_ms_from_spans_leaves_out_fences_and_cut_steps():
    # step 1: 35 ms less a 22 ms fence; step 2: 52 ms less 40 ms
    assert PS.step_host_ms(SPANS, 0, 100 * MS) == pytest.approx(12.5)
    assert PS.step_host_ms(SPANS, 0, 60 * MS) == pytest.approx(13.0)
    assert PS.step_host_ms(SPANS, 40 * MS, 60 * MS) is None


def _ctx(steps, w0=0.0, w1=10.0):
    stats = types.SimpleNamespace(steps=steps)
    rec = types.SimpleNamespace(w0=w0, w1=w1)
    return types.SimpleNamespace(stats=stats, rec=rec, trace=None)


def test_step_host_ms_reader_reads_the_traced_steps():
    from repro.runtime.batching import StepRecord
    read = spec.load_reader("step_host_ms")
    lo = 10.0 - bench.TRACE_S
    steps = [StepRecord(lo - 0.5, 90.0, 10.0, 7),      # before the trace
             StepRecord(lo, 80.0, 74.0, 7),            # 6 ms of host
             StepRecord(lo + 1.0, 90.0, 82.0, 3),      # 8 ms
             StepRecord(9.99, 70.0, 60.0, 4),          # 10 ms, ends later
             StepRecord(10.0, 50.0, 0.0, 4)]           # after the window
    assert read(_ctx(steps)) == pytest.approx(8.0)
    # a window shorter than the trace: the profiler ran from its start
    assert read(_ctx(steps, w0=lo + 0.5)) == pytest.approx(9.0)
    # a program without step records (the parent's) reads nothing
    assert read(types.SimpleNamespace(stats=object(), rec=None)) is None
    assert read(_ctx([])) is None


HLO = """HloModule jit__paged_prefill

%fused_computation.1 (p0: bf16[16,4,8]) -> bf16[16,4,8] {
  ROOT %copy.9 = bf16[16,4,8]{2,1,0} copy(bf16[16,4,8]{2,1,0} %p0)
}

ENTRY %main {
  %dynamic_slice.3 = bf16[1,516,16,32,80]{4,3,2,1,0} dynamic-slice(%pool, %i), metadata={op_name="jit(_paged_prefill)/while/body/kv_view/dynamic_slice"}
  %copy.42 = bf16[1,516,16,32,80]{1,4,3,2,0} copy(bf16[1,516,16,32,80]{4,3,2,1,0} %dynamic_slice.3)
  %bitcast.7 = bf16[1,2064,32,80]{3,2,1,0} bitcast(%copy.42)
  %copy.43 = bf16[1,2064,32,80]{1,3,2,0} copy(bf16[1,2064,32,80]{3,2,1,0} %bitcast.7)
  %copy.44 = bf16[128,2560]{0,1} copy(bf16[128,2560]{1,0} %x)
  %fusion.5 = bf16[8,516,16,32,80]{4,3,2,1,0} fusion(%pool, %copy.43), kind=kLoop, metadata={op_name="jit(_paged_prefill)/while/body/closed_call/kv_write/dynamic_update_slice"}
  %convolution.3 = bf16[128,7680]{1,0} convolution(bf16[128,2560]{1,0} %copy.44, bf16[2560,7680]{1,0} %w), metadata={op_name="jit(_paged_prefill)/while/body/dot_general"}
  %pad.1 = bf16[1,2560,32,80]{3,2,1,0} pad(%copy.43, %zero), metadata={op_name="jit(_paged_prefill)/while/body/kv_view/jit(_pad)/pad"}
  %while.2 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_paged_prefill)/while"}
  %mask.1 = s32[1,2064]{1,0} select(%a, %b, %c), metadata={op_name="jit(_paged_prefill)/kv_viewer/select_n"}
}
"""


def test_kv_ops_follow_the_scopes_and_the_copies_of_them():
    assert PS.kv_op_names(HLO) == {"dynamic_slice.3", "copy.42",
                                   "bitcast.7", "copy.43", "fusion.5",
                                   "pad.1"}
    assert TR.gemm_op_names(HLO) == {"convolution.3"}


def test_kv_share_of_the_full_width_prefill_runs():
    ops = [("fusion.5", 1 * MS, 3 * MS), ("convolution.3", 3 * MS, 9 * MS),
           ("pad.1", 21 * MS, 22 * MS), ("fusion.5", 31 * MS, 33 * MS)]
    modules = [("jit__paged_prefill(1)", 0, 10 * MS),
               ("jit__paged_prefill(1)", 20 * MS, 25 * MS),
               ("jit__paged_prefill(1)", 30 * MS, 40 * MS)]
    tr = TR.Trace(ops, modules, [("window", 0, 50 * MS)], 0, 50 * MS)
    ctx = bench.Context(cell=None, sizes={}, setup_s=0.0, rec=None,
                        stats=None, prefill_ticks=[], decode_ticks=[],
                        full_width=[], peaks=None, chunk=128, trace=tr,
                        traced=[W.Dispatch(0.01, "prefill", width=128),
                                W.Dispatch(0.02, "prefill", width=32),
                                W.Dispatch(0.04, "prefill", width=128)],
                        modules={"prefill": "jit__paged_prefill"})
    # full-width runs 0-10 and 30-40 ms hold 2 + 2 ms of KV ops
    assert PS.kv_share(ctx, {"fusion.5", "pad.1"}) == pytest.approx(20.0)
    assert PS.kv_share(ctx, set()) is None


def _tiny_engine_hlo():
    cell = _tiny.cell("prefill_sat")
    sizes = dict(cell.model_sizes(), **_tiny.TINY)
    eng = bench.build(cell, _tiny.SEED, sizes)
    s = cell.settings
    return adapter.step_hlo(
        eng, slots=s["slots"], chunk=s["prefill_chunk"],
        page_size=s["page_size"],
        num_pages=s["slots"] * (s["max_len"] // s["page_size"]))["prefill"]


def test_kv_ops_of_the_paged_prefill_step_and_unchanged_gemm_picks(
        monkeypatch):
    hlo = _tiny_engine_hlo()
    kv = PS.kv_op_names(hlo)
    opcodes = {TR._INSTR.match(line).group(2) for line in hlo.splitlines()
               if TR._INSTR.match(line)
               and TR._INSTR.match(line).group(1) in kv}
    # the pool's per-layer slice and write-back, the page scatter, the
    # view's gather and its pad to the attention block
    assert {"dynamic-slice", "dynamic-update-slice", "scatter", "gather",
            "pad"} <= opcodes
    gemms = TR.gemm_op_names(hlo)
    assert gemms and not gemms & kv
    # the same step traced without the KV path's and the head's scopes
    # picks the same GEMM instructions
    scope = jax.named_scope

    def unscoped(name):
        if name in ("kv_write", "kv_view", "lm_head"):
            return contextlib.nullcontext()
        return scope(name)

    monkeypatch.setattr(jax, "named_scope", unscoped)
    bare = _tiny_engine_hlo()
    assert PS.kv_op_names(bare) == set()
    assert TR.gemm_op_names(bare) == gemms


def test_reads_the_program_spans_of_a_real_profile(tmp_path):
    from repro import obs
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=bench._profile_options())
    with jax.profiler.TraceAnnotation("window"):
        with obs.use_tracer(obs.Tracer(sink="profiler")):
            with obs.span("sched_step", tick=1):
                with obs.span("fence"):
                    f(x).block_until_ready()
                obs.instant("prefix_hit", rid=0)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = PS.read(path)
    assert sorted(s[0] for s in spans) == ["fence", "prefix_hit",
                                           "sched_step"]
    step = next(s for s in spans if s[0] == "sched_step")
    fence = next(s for s in spans if s[0] == "fence")
    assert step[1] <= fence[1] and fence[2] <= step[2]
    # the runtime's own host events (PjitFunction, Execute) are left out
    assert all(s[0] in ("fence", "prefix_hit", "sched_step") for s in spans)
    tr = TR.from_xplane(path)
    assert PS.step_host_ms(spans, tr.t0, tr.t1) >= 0


def test_spantrace_serves_with_and_without_the_sink_off_a_chip():
    from chipbench import spantrace
    cell = _tiny.cell("prefill_sat")
    sizes = dict(cell.model_sizes(), **_tiny.TINY)
    t = time.perf_counter()
    lines = list(spantrace.run(cell, [_tiny.SEED], 2.0, paired=True,
                               require_tpu=False, sizes=sizes))
    assert time.perf_counter() - t < 120
    assert [ln["sink"] for ln in lines] == [True, False]
    on, off = lines
    assert on["program_spans"] > 0 and off["program_spans"] == 0
    assert on["kv_ops"] > 0 and on["gemm_ops"] > 0
    # no time, rate or device share off a chip
    for ln in lines:
        assert "metrics" not in ln and "idle_by_span" not in ln
