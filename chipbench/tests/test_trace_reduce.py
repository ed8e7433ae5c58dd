"""Trace reduction on a synthetic trace and on a real (CPU) profile."""
import glob
import os

import pytest

from chipbench import bench, trace_reduce as TR
from chipbench import window as W

MS = 1_000_000


def _trace():
    # window 0..100 ms; two program runs with ops inside, one gap
    ops = [("fusion.1", 10 * MS, 20 * MS), ("convert.2", 15 * MS, 30 * MS),
           ("fusion.1", 50 * MS, 60 * MS), ("copy.3", 95 * MS, 120 * MS),
           ("fusion.9", -5 * MS, 2 * MS)]
    modules = [("jit__paged_prefill(1)", 10 * MS, 30 * MS),
               ("jit__paged_prefill(1)", 50 * MS, 60 * MS),
               ("jit__paged_decode(2)", 95 * MS, 100 * MS)]
    host = [("window", 0, 100 * MS), ("step", 5 * MS, 34 * MS),
            ("generator", 35 * MS, 45 * MS), ("step", 49 * MS, 100 * MS)]
    return TR.Trace(ops, modules, host, 0, 100 * MS)


def test_busy_union_and_idle_share():
    tr = _trace()
    # union inside the window: 0-2, 10-30, 50-60, 95-100 = 37 ms
    assert TR.busy_ns(tr) == 37 * MS
    assert TR.idle_share(tr) == pytest.approx(0.63)
    assert tr.window_s == pytest.approx(0.1)


def test_op_times_and_programs():
    tr = _trace()
    top = dict(TR.top_ops(tr))
    assert top["fusion.1"] == pytest.approx(0.020)
    assert top["copy.3"] == pytest.approx(0.005)       # clipped at 100 ms
    runs = TR.modules_matching(tr, "jit__paged_prefill")
    assert len(runs) == 2
    assert TR.op_ns_in(tr, runs, {"fusion.1", "convert.2"}) == 35 * MS
    assert TR.op_ns_in(tr, runs[:1], {"fusion.1"}) == 10 * MS


def test_idle_gaps_are_labelled_by_the_host_span():
    gaps = TR.idle_gaps(_trace())
    assert gaps[0] == ["step", pytest.approx(0.035)]     # 60-95 ms
    assert ["generator", pytest.approx(0.020)] in gaps   # 30-50 ms
    assert sum(g for _, g in gaps) == pytest.approx(0.063)


HLO = """HloModule jit__paged_prefill, entry_computation_layout={...}

%fused_computation.1 (p0: f32[16,2560,7680]) -> bf16[16,2560,7680] {
  ROOT %convert.4 = bf16[16,2560,7680]{2,1,0} convert(f32[16,2560,7680]{2,1,0} %p0)
}

ENTRY %main {
  %params__layers_attn_wqkv___data.12 = f32[16,2560,7680]{2,1,0} parameter(3)
  %convert_fusion.7 = bf16[16,2560,7680]{2,1,0} fusion(f32[16,2560,7680]{2,1,0} %params__layers_attn_wqkv___data.12), kind=kLoop, calls=%fused_computation.1
  %convolution.3 = bf16[128,7680]{1,0} convolution(bf16[128,2560]{1,0} %x, bf16[2560,7680]{1,0} %w), dim_labels=bf_io->bf, metadata={op_name="jit(_paged_prefill)/while/body/dot_general"}
  %fusion.5 = bf16[128,32,80]{2,1,0} fusion(bf16[128,7680]{1,0} %convolution.3), kind=kOutput, metadata={op_name="jit(_paged_prefill)/while/body/flash_attn/dot_general"}
  %add.9 = bf16[128,2560]{1,0} add(bf16[128,2560]{1,0} %a, bf16[128,2560]{1,0} %b), metadata={op_name="jit(_paged_prefill)/while/body/add"}
  %custom-call.2 = f32[128,2560]{1,0} custom-call(bf16[128,2560]{1,0} %y), custom_call_target="tpu_custom_call", metadata={op_name="jit(_paged_prefill)/panel_gemm/pallas_call"}
}
"""


def test_gemm_ops_are_picked_from_the_step_hlo():
    names = TR.gemm_op_names(HLO)
    assert names == {"convert_fusion.7", "convolution.3", "custom-call.2"}
    assert TR.module_name(HLO) == "jit__paged_prefill"


def test_program_runs_pair_with_dispatches_in_order():
    tr = _trace()
    ctx = bench.Context(cell=None, sizes={}, setup_s=0.0, rec=None,
                        stats=None, prefill_ticks=[], decode_ticks=[],
                        full_width=[], peaks=None, chunk=128, trace=tr,
                        traced=[W.Dispatch(0.03, "prefill", width=128),
                                W.Dispatch(0.06, "prefill", width=32),
                                W.Dispatch(0.1, "decode")],
                        modules={"prefill": "jit__paged_prefill",
                                 "decode": "jit__paged_decode"})
    pairs = ctx.module_runs("prefill")
    assert [(d.width, r[1]) for d, r in pairs] == [(128, 10 * MS),
                                                   (32, 50 * MS)]
    ctx.traced = ctx.traced[:1]          # a run the host did not dispatch
    assert ctx.module_runs("prefill") is None


def test_reads_a_real_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=bench._profile_options())
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    tr = TR.from_xplane(path)
    assert [h[0] for h in tr.host].count("window") == 1
    assert any(h[0] == "step" for h in tr.host)
    assert tr.t1 > tr.t0
