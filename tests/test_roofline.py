"""Roofline tests: collective parsing, wire-byte formulas, the loop-aware
HLO cost walker on crafted modules, and model_flops accounting."""
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.models import model_zoo
from repro.roofline import analysis as R
from repro.roofline.hlo_cost import HloCostModel, parse_module

HLO = """HloModule test, num_partitions=8

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%x, %y)
}

%cond (p: (s32[], f32[64,128])) -> pred[] {
  %p = (s32[], f32[64,128]{1,0}) parameter(0)
  %gte = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%gte, %c), direction=LT
}

%body (p: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {
  %p = (s32[], f32[64,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant({...})
  %dot.1 = f32[64,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,128]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,128]{1,0}) tuple(%ip, %ar)
}

ENTRY %main (a: f32[64,128], b: s32[]) -> f32[64,128] {
  %a = f32[64,128]{1,0} parameter(0)
  %b = s32[] parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,128]{1,0}) tuple(%zero, %a)
  %wh = (s32[], f32[64,128]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[64,128]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_parse_module_structure():
    comps, entry = parse_module(HLO)
    assert entry == "main"
    assert set(comps) >= {"add", "cond", "body", "main"}
    assert comps["cond"].consts == [5]


def test_walker_scales_by_trip_count():
    m = HloCostModel(HLO, total_devices=8)
    c = m.cost()
    # 5 trips x dot(64x128 @ 128x128) = 5 * 2*64*128*128
    assert c.flops == 5 * 2 * 64 * 128 * 128
    # 5 trips x all-reduce over group of 4: 2 * B * 3/4
    ar = 64 * 128 * 4
    assert c.coll_wire_bytes == pytest.approx(5 * 2 * ar * 3 / 4)
    assert m.loops == [{"body": "body", "trips": 5, "in": "main"}]


def test_wire_bytes_formulas():
    assert R._wire_bytes("all-gather", 1000, 4) == pytest.approx(750)
    assert R._wire_bytes("all-reduce", 1000, 4) == pytest.approx(1500)
    assert R._wire_bytes("reduce-scatter", 1000, 4) == pytest.approx(3000)
    assert R._wire_bytes("collective-permute", 1000, 4) == 1000
    assert R._wire_bytes("all-reduce", 1000, 1) == 0.0


def test_parse_collectives_iota_and_list_groups():
    text = (
        "  %ar = f32[128]{0} all-reduce(%x), replica_groups=[4,2]<=[8]\n"
        "  %ag = bf16[256]{0} all-gather(%y), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}\n")
    ops = R.parse_collectives(text, 8)
    assert len(ops) == 2
    assert ops[0].group_size == 2
    assert ops[0].result_bytes == 512
    assert ops[1].group_size == 4
    assert ops[1].result_bytes == 512


def test_async_start_done_counted_once():
    text = (
        "  %s = f32[128]{0} all-gather-start(%x), "
        "replica_groups=[2,4]<=[8]\n"
        "  %d = f32[128]{0} all-gather-done(%s)\n")
    m = HloCostModel("ENTRY %e (p: f32[]) -> f32[] {\n" + text + "}\n",
                     total_devices=8)
    c = m.cost()
    assert c.coll_wire_bytes == pytest.approx(512 * 3 / 4)


def test_model_flops_by_kind():
    cfg = model_zoo.get_config("deepseek-7b")
    n = cfg.active_param_count()
    tr = R.model_flops(cfg, SHAPES["train_4k"])
    pf = R.model_flops(cfg, SHAPES["prefill_32k"])
    dc = R.model_flops(cfg, SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert pf == pytest.approx(2 * n * 32 * 32768)
    assert dc == pytest.approx(2 * n * 128)


def test_moe_active_params_smaller():
    cfg = model_zoo.get_config("qwen3-moe-30b-a3b")
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
    # ~30B total / ~3B active per the model card
    assert 25e9 < cfg.param_count() < 35e9
    assert 2e9 < cfg.active_param_count() < 4.5e9


def test_param_counts_sane():
    """Analytic param counts near each arch's nameplate size."""
    expect = {
        "deepseek-7b": (6e9, 8e9),
        "gemma2-9b": (8e9, 11e9),
        "internvl2-76b": (68e9, 82e9),
        "deepseek-v3-671b": (600e9, 720e9),
        "mamba2-370m": (0.30e9, 0.45e9),
        "hymba-1.5b": (1.2e9, 1.9e9),
        "stablelm-3b": (2.5e9, 3.6e9),
        "h2o-danube-3-4b": (3.2e9, 4.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = model_zoo.get_config(arch).param_count()
        assert lo < n < hi, (arch, n)


def test_roofline_terms_dominance():
    coll = {"seconds": 0.5, "dcn_seconds": 0.0, "by_kind": {},
            "num_ops": 1, "wire_bytes": 1.0}
    t = R.roofline_terms(flops_per_device=197e12 * 0.1,   # 0.1 s compute
                         bytes_per_device=819e9 * 0.2,    # 0.2 s memory
                         collective=coll, chips=256,
                         model_fl=1e15, hw=R.V5E)
    assert t["dominant"] == "collective"
    assert t["bound_s"] == pytest.approx(0.5)
    assert t["compute_s"] == pytest.approx(0.1)
    assert t["memory_s"] == pytest.approx(0.2)


def test_device_peaks_keyed_by_device_kind():
    v5e = R.device_peaks("TPU v5 lite")
    assert v5e is R.V5E
    assert (v5e["peak_flops_bf16"], v5e["peak_ops_int8"], v5e["hbm_bw"]) \
        == (197e12, 393e12, 819e9)
    assert "peak_flops_fp32" not in v5e        # no published figure
    # a kind the table does not list has no roofline, the CPU included
    assert R.device_peaks("cpu") is None
    import jax
    if jax.devices()[0].platform == "cpu":
        assert R.device_peaks() is None
