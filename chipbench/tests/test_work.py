"""Operations and bytes of the serving steps against counts by hand at
the two configurations' published widths."""
import pytest

from chipbench import spec, work

# per layer: fused QKV [d, 3d], wo [d, d], fused gate/up [d, 2 d_ff],
# w_down [d_ff, d]; no grouped KV in either model
HAND = {
    "stablelm-3b-16l": dict(L=16, d=2560, dff=6912, V=50304,
                            layer=2560 * 7680 + 2560 * 2560
                            + 2560 * 13824 + 6912 * 2560),
    "deepseek-7b-8l": dict(L=8, d=4096, dff=11008, V=102400,
                           layer=4096 * 12288 + 4096 * 4096
                           + 4096 * 22016 + 11008 * 4096),
}


def _sizes(name):
    b = spec.benchmark()
    w = next(w for w in b["workloads"] if w["config"] == name)
    return spec.cell(w["name"], b).model_sizes()


@pytest.mark.parametrize("name", sorted(HAND))
def test_layer_parameters(name):
    s, h = _sizes(name), HAND[name]
    assert work.layer_params(s) == h["layer"]
    assert s["num_layers"] == h["L"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_chunk_counts(name):
    s, h = _sizes(name), HAND[name]
    d, L = h["d"], h["L"]
    start, end, width = 256, 356, 128          # a 100-token final chunk
    keys = sum(range(start + 1, end + 1))      # causal: row i sees i+1 keys
    flops = (2 * 100 * L * h["layer"]          # projections, real rows
             + 4 * d * keys * L                # QK^T and PV, all heads
             + 2 * d * h["V"])                 # the head on one row
    w = work.prefill_chunk(s, width, start, end)
    assert w.model_flops == pytest.approx(flops, rel=1e-12)
    assert len(w.gemms) == 4 * L + 1
    f0, b0 = w.gemms[0]                        # fused QKV at 128 rows
    assert f0 == 2 * 128 * d * 3 * d
    assert b0 == d * 3 * d * 4 + 128 * d * 2 + 128 * 3 * d * 2
    fh, bh = w.gemms[-1]                       # head: one row, f32 out
    assert fh == 2 * d * h["V"]
    assert bh == d * h["V"] * 4 + d * 2 + h["V"] * 4


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_counts(name):
    s, h = _sizes(name), HAND[name]
    d, L = h["d"], h["L"]
    ctx = [300, 17, 512]                       # three live slots of 16
    w = work.decode_step(s, 16, ctx)
    assert w.model_flops == pytest.approx(
        2 * 3 * L * h["layer"] + 4 * d * sum(ctx) * L
        + 2 * 3 * d * h["V"], rel=1e-12)
    fd, bd = w.gemms[3]                        # w_down at 16 rows
    assert fd == 2 * 16 * h["dff"] * d
    assert bd == h["dff"] * d * 4 + 16 * h["dff"] * 2 + 16 * d * 2


def test_roofline_time_takes_the_larger_bound():
    w = work.Work(0.0, [(197e12, 1.0), (1.0, 819e9)])
    assert w.gemm_roofline_s(197e12, 819e9) == pytest.approx(2.0)
