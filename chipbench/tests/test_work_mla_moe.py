"""Operations and bytes of the DeepSeek-V2 cell's prefill steps against
counts by hand at its published widths, and what the two readers built
on them read (CPU: stand-in contexts, no device time)."""
import types

import numpy as np
import pytest

from chipbench import spec, trace_reduce, work_mla_moe
from chipbench import window as W

CELL = "deepseek-v2-lite-14l.long_prefill"


def _cell():
    return spec.cell(CELL)


def test_prefill_model_flops_by_hand():
    c = _cell()
    cfg, s = c.config, c.model_sizes()
    d, h, L, V = 2048, 16, 14, 102400
    # W_q [d, 16 x 192], W_kv_a [d, 576], W_kv_b [512, 16 x 256],
    # W_o [16 x 128, d]
    proj = d * 3072 + d * 576 + 512 * 4096 + 2048 * d
    start, end = 4096, 5120                     # a full 1024-token chunk
    keys = sum(range(start + 1, end + 1))
    rows = [[96] * 8] * 13                      # 13 MoE layers, 8 held
    flops = 2 * (L * (1024 * proj + h * (192 + 128) * keys)
                 + 1024 * 3 * d * 10944                 # layer 0's SwiGLU
                 + 13 * 1024 * d * (3 * 2816 + 64)      # shared, router
                 + 13 * 8 * 96 * 3 * d * 1408           # held experts
                 + d * V)                               # the head's row
    dsp = W.Dispatch(t=0.0, kind="prefill", width=1024, start=start, end=end)
    assert work_mla_moe.model_flops(cfg, s, dsp, rows) == pytest.approx(
        flops, rel=1e-12)


def test_expert_gemm_terms_by_hand():
    c = _cell()
    rows = [[10, 0, 5, 1, 0, 0, 0, 4]] * 13
    terms = work_mla_moe.expert_gemms(c.config, c.model_sizes(), rows)
    assert len(terms) == 26                     # gate/up and down per layer
    (fg, bg), (fd, bd) = terms[:2]
    assert fg == 2 * 20 * 2048 * 2816
    assert bg == 8 * 2048 * 2816 * 4 + 20 * 2048 * 2 + 20 * 2816 * 4
    assert fd == 2 * 20 * 1408 * 2048
    assert bd == 8 * 1408 * 2048 * 4 + 20 * 1408 * 2 + 20 * 2048 * 4


def _ctx(n_prefill, rows):
    dsp = [W.Dispatch(t=float(i), kind="prefill", width=1024,
                      start=1024 * i, end=1024 * (i + 1))
           for i in range(n_prefill)]
    dsp.insert(1, W.Dispatch(t=0.5, kind="decode", contexts=(5,)))
    stats = types.SimpleNamespace(
        expert_rows_of=lambda phase: np.asarray(rows, np.int32))
    return types.SimpleNamespace(rec=types.SimpleNamespace(dispatches=dsp),
                                 stats=stats), dsp


def test_rows_pair_with_the_runs_prefill_dispatches():
    rows = [[[i, 2 * i]] for i in range(3)]
    ctx, dsp = _ctx(3, rows)
    traced = [d for d in dsp if d.kind == "prefill"][1:]
    assert work_mla_moe.prefill_rows(ctx, traced) == [[[1, 2]], [[2, 4]]]
    ctx, _ = _ctx(4, rows)                      # a dispatch without rows
    assert work_mla_moe.prefill_rows(ctx, traced) is None
    bare = types.SimpleNamespace(rec=ctx.rec, stats=object())
    assert work_mla_moe.prefill_rows(bare, traced) is None


def test_the_grouped_gemm_ops_are_found_by_their_labels():
    from chipbench.metrics import _device  # noqa: F401  (package import)
    import importlib.util
    spec_ = importlib.util.spec_from_file_location(
        "egr", spec.metric_file("expert_gemm_roofline.prefill"))
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    labels = [
        "%ragged-dot-none = f32[6144,2816]{1,0} custom-call(%a, %b)",
        "%ragged-dot-none.1 = f32[6144,2048]{1,0} custom-call(%a, %b)",
        "%ragged-dot-metadata = (s32[9], s32[19]) custom-call(%g)",
        "%fusion.3 = bf16[1024,2048]{1,0} fusion(%p), kind=kLoop",
        "%convolution.7 = f32[1024,3648]{1,0} convolution(%x, %w)",
    ]
    picked = {trace_reduce.op_event(t)[0] for t in labels
              if mod._is_grouped(trace_reduce.op_event(t)[1])}
    assert picked == {"ragged-dot-none", "ragged-dot-none.1",
                      "ragged-dot-metadata"}


@pytest.mark.parametrize("name", ["expert_gemm_roofline.prefill",
                                  "mla_moe_mfu.prefill"])
def test_readers_read_nothing_without_a_trace(name):
    ctx = types.SimpleNamespace(module_runs=lambda kind: None, peaks=None,
                                trace=None)
    assert spec.load_reader(name)(ctx) is None
