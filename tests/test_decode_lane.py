"""Decode fast-lane gates: split-K kernel/oracle bitwise determinism,
the decode policy arm, decode M buckets, plan warmup, and the megastep
serving stats.

The split-K discipline is the paper's bit-exactness protocol extended
to the reduction dimension: for every split_k the recombined kernel
result must be BIT-IDENTICAL to ``kernels/ref.gemm_splitk`` — per-slice
blocked partials summed by the shared deterministic fixed-order tree —
for fp32 and both quantized formats, with and without fused epilogues.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import gemm
from repro.core import bitexact, packing
from repro.gemm import backends as B
from repro.kernels import panel_gemm as K
from repro.kernels import ref
from repro.quant import formats as F
from repro.quant import kernels as QK

BM, BN, BK = 8, 128, 128
SPLITS = (1, 2, 4, 8)


def _operands(split_k, n=BN, seed=0):
    rng = np.random.default_rng(seed)
    k = 2 * BK * split_k           # every slice carries a real K-carry
    x = jnp.asarray(rng.standard_normal((BM, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    return x, w


# ------------------------------------------------- bitwise determinism
@pytest.mark.parametrize("split_k", SPLITS)
def test_splitk_kernel_bitwise_vs_oracle_fp32(split_k):
    x, w = _operands(split_k)
    y = K.panel_gemm_splitk(x, w, split_k=split_k, block_m=BM,
                            block_n=BN, block_k=BK, interpret=True)
    oracle = ref.gemm_splitk(x, w, BK, split_k)
    bitexact.assert_bit_identical(np.asarray(y), np.asarray(oracle),
                                  f"split_k={split_k}")


@pytest.mark.parametrize("fmt", ["int8", "ternary"])
@pytest.mark.parametrize("split_k", SPLITS)
def test_splitk_kernel_bitwise_vs_oracle_quant(fmt, split_k):
    x, w = _operands(split_k, seed=1)
    q, s = F.quantize(w, fmt)
    data = F.pack_ternary_codes(q) if fmt == "ternary" else q
    y = QK.quant_panel_gemm_splitk(x, data, s, weight_format=fmt,
                                   split_k=split_k, block_m=BM,
                                   block_n=BN, block_k=BK,
                                   interpret=True)
    oracle = ref.gemm_splitk(x, F.dequantize_padded(data, s, fmt), BK,
                             split_k)
    bitexact.assert_bit_identical(np.asarray(y), np.asarray(oracle),
                                  f"{fmt} split_k={split_k}")


@pytest.mark.parametrize("spec", [
    gemm.EpilogueSpec(bias=True),
    gemm.EpilogueSpec(act="silu", residual=True),
    gemm.EpilogueSpec(softcap=30.0),
    gemm.EpilogueSpec(glu="silu"),
])
def test_splitk_epilogue_composes_bitwise(spec):
    """Every EpilogueSpec applies on the COMBINED fp32 accumulator via
    the shared apply_epilogue — bit-identical to oracle + jnp epilogue."""
    split_k = 2
    rng = np.random.default_rng(2)
    k = 2 * BK * split_k
    n = 2 * BN if spec.glu else BN
    n_out = BN if spec.glu else n
    x = jnp.asarray(rng.standard_normal((BM, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    bias = (jnp.asarray(rng.standard_normal((n,)), jnp.float32)
            if spec.bias else None)
    res = (jnp.asarray(rng.standard_normal((BM, n_out)), jnp.float32)
           if spec.residual else None)
    y = K.panel_gemm_splitk(x, w, bias, res, split_k=split_k, block_m=BM,
                            block_n=BN, block_k=BK, epilogue=spec,
                            interpret=True)
    acc = ref.gemm_splitk(x, w, BK, split_k, out_dtype=jnp.float32)
    oracle = jax.jit(lambda a, b, r: K.apply_epilogue(
        a, spec, bias=b, residual=r).astype(jnp.float32))(acc, bias, res)
    bitexact.assert_bit_identical(np.asarray(y), np.asarray(oracle),
                                  f"epilogue={spec}")


def test_splitk_validate_plan_gates():
    """plan(validate=True) runs the split-K gate for fp32 and quant."""
    for fmt in ("fp32", "int8", "ternary"):
        p = gemm.plan(BM, BN, 4 * BK, block_m=BM, block_n=BN, block_k=BK,
                      split_k=4, decode=True, weight_format=fmt,
                      validate=True)
        assert p.validated and p.split_k == 4
        assert gemm.validate_plan(p)


def test_splitk_one_degenerates_to_blocked():
    x, w = _operands(1)
    a = ref.gemm_splitk(x, w, BK, 1)
    b = ref.gemm_blocked(x, w, BK)
    bitexact.assert_bit_identical(np.asarray(a), np.asarray(b),
                                  "split_k=1 vs blocked")


def test_splitk_combine_fixed_tree_order():
    """The combine is the static pairwise tree, not a left fold."""
    parts = [jnp.full((1, 1), float(v)) for v in (1e16, 1.0, 1.0, -1e16)]
    tree = np.asarray(gemm.splitk_combine(parts))[0, 0]
    # tree: (1e16 + 1) + (1 - 1e16) = 1e16 + (1 - 1e16) = 0.0
    # fold: ((1e16 + 1) + 1) - 1e16 = 0.0 too — distinguish with order
    parts2 = [jnp.full((1, 1), float(v)) for v in (1.0, 1e16, -1e16, 1.0)]
    tree2 = np.asarray(gemm.splitk_combine(parts2))[0, 0]
    # tree: (1 + 1e16) + (-1e16 + 1) = 1e16 + (1 - 1e16) = 0.0
    # fold: ((1 + 1e16) - 1e16) + 1 = 1.0
    assert tree == 0.0 and tree2 == 0.0
    # odd count: trailing partial rides up unpaired
    odd = [jnp.full((1, 1), float(v)) for v in (1.0, 2.0, 3.0)]
    assert np.asarray(gemm.splitk_combine(odd))[0, 0] == 6.0


# ---------------------------------------------- xla backend split path
def test_xla_splitk_run_bitwise_vs_slice_reference():
    rng = np.random.default_rng(3)
    n, k, s = 256, 1024, 4
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    pw = packing.pack(w, block_n=128, block_k=256)
    p = gemm.plan(4, n, k, backend="xla", block_n=128, block_k=256,
                  pack=gemm.PACK_PREPACKED, decode=True, split_k=s)
    y = gemm.execute(p, x, pw)
    ks = k // s
    parts = [jnp.dot(x[:, i * ks:(i + 1) * ks], w[i * ks:(i + 1) * ks],
                     preferred_element_type=jnp.float32)
             for i in range(s)]
    yref = jax.jit(lambda ps: gemm.splitk_combine(ps))(parts)
    bitexact.assert_bit_identical(np.asarray(y),
                                  np.asarray(yref.astype(y.dtype)),
                                  "xla split-K execute")


@pytest.mark.parametrize("fmt", ["fp32", "int8", "ternary"])
def test_decode_lane_bitwise_vs_prefill_plans(fmt):
    """The decode-arm plan (prepacked, skinny block_m, split_k=1 on xla)
    computes the same bits as the prefill policy's plans for the shape:
    the prepacked plan on the same pack, and the per-call plan on the
    checkpoint-layout [N, K] weight (dequantized for quant formats)."""
    rng = np.random.default_rng(9)
    slots, n, k = 2, 256, 1024
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.standard_normal((slots, k)), jnp.float32)
    quant = None if fmt == "fp32" else fmt
    bn, bk = gemm.pack_blocks(n, k, weight_format=fmt)
    pw = packing.pack(w, block_n=bn, block_k=bk, quant=quant)
    lane = gemm.plan_for_packed(slots, pw, backend="xla", decode=True)
    assert lane.decode and lane.split_k == 1
    prefill = gemm.plan_for_packed(slots, pw, backend="xla", decode=False)
    assert not prefill.decode
    percall = gemm.plan(slots, n, k, backend="xla", transposed=True,
                        pack=gemm.PACK_PERCALL, block_n=pw.block_n,
                        block_k=pw.block_k)
    if quant:
        q, s = F.quantize(w, fmt)
        w = q.astype(jnp.float32) * F.expand_scales(s, k)
    y_lane = np.asarray(gemm.execute(lane, x, pw))
    bitexact.assert_bit_identical(
        y_lane, np.asarray(gemm.execute(prefill, x, pw)),
        f"{fmt}: decode lane vs prefill prepacked plan")
    bitexact.assert_bit_identical(
        y_lane, np.asarray(gemm.execute(percall, x, w.T)),
        f"{fmt}: decode lane vs per-call plan")


def test_execute_rejects_undivisible_split():
    w = jnp.zeros((512, 128), jnp.float32)
    pw = packing.pack(w, block_n=128, block_k=256)
    p = gemm.plan(4, 128, 512, backend="xla", block_n=128, block_k=256,
                  pack=gemm.PACK_PREPACKED, decode=True, split_k=2)
    assert p.split_k == 2          # 512 / 2 = 256-deep slices: fine
    with pytest.raises(ValueError):
        gemm.plan(4, 128, 512, block_n=128, block_k=256, decode=True,
                  split_k=4)      # 128-deep slices < block_k


# ------------------------------------------------------ decode policy arm
def test_decode_lane_scope_and_plan_keying():
    gemm.plan_cache_clear()
    with gemm.decode_lane():
        assert gemm.in_decode_lane()
        pd = gemm.plan(4, 1024, 4096)
    assert not gemm.in_decode_lane()
    pp = gemm.plan(4, 1024, 4096)
    assert pd.decode and not pp.decode
    assert pd.pack == gemm.PACK_PREPACKED       # decode arm forces prepack
    assert pp.pack == gemm.PACK_PERCALL         # fine lever's default
    assert pd.block_m == 8
    # distinct cache entries for the same (m, n, k)
    assert gemm.plan_cache_info().misses >= 2


def test_decode_split_k_is_m_independent():
    """The slice map must be a pure function of (n, k, format): serve
    decodes at M = slots, generate at M = batch — same split, or the
    two paths' tokens diverge bitwise.  (On the panel-grid backends —
    occupancy is a grid property, so the shape-agnostic xla backend
    keeps split_k = 1 by policy.)"""
    with gemm.decode_lane():
        plans = [gemm.plan(m, 256, 2048, backend="interpret")
                 for m in (1, 2, 4, 8, 16)]
    assert len({p.split_k for p in plans}) == 1
    # narrow-N deep-K decode shapes actually engage the reduction lever
    assert plans[0].split_k > 1
    assert all(p.block_m == 8 for p in plans)   # pinned skinny panel


def test_decode_split_k_only_on_grid_backends():
    """The occupancy model scores kernel-grid panels; the xla backend
    has no grid, and the restructure measured a wash-to-loss on CPU —
    policy keeps split_k = 1 there (explicit split_k= still works)."""
    with gemm.decode_lane():
        p_xla = gemm.plan(4, 256, 2048, backend="xla")
        p_krn = gemm.plan(4, 256, 2048, backend="interpret")
    assert p_xla.split_k == 1 and p_xla.decode
    assert p_krn.split_k > 1
    p_exp = gemm.plan(4, 256, 2048, backend="xla", decode=True,
                      split_k=2)
    assert p_exp.split_k == 2


def test_decode_arm_prefill_shapes_unsplit():
    """The prefill row panel keeps split_k == 1 (occupancy already comes
    from the (M/bm, N/bn) grid there)."""
    p = gemm.plan(128, 1024, 4096)
    assert p.split_k == 1 and not p.decode


def test_decode_buckets():
    assert [gemm.bucket_m(m, decode=True) for m in (1, 2, 3, 4, 5, 8)] \
        == [1, 2, 4, 4, 8, 8]
    # beyond the decode buckets: falls through to the prefill ladder
    assert gemm.bucket_m(9, decode=True) == 16
    assert gemm.bucket_m(129, decode=True) == 256
    # prefill bucketing unchanged (the aliasing the decode buckets fix)
    assert [gemm.bucket_m(m) for m in (1, 2, 4, 8)] == [8, 8, 8, 8]
    with pytest.raises(ValueError):
        gemm.bucket_m(0, decode=True)


def test_scheduler_scores_splitk_occupancy():
    """The napkin model: split-K restores reduction-side occupancy at
    skinny M / narrow N, and charges the combine cost."""
    from repro.core import scheduler
    base = scheduler.plan(8, 256, 2048, block_m=8, block_n=128,
                          block_k=512, num_cores=8)
    split = scheduler.plan(8, 256, 2048, block_m=8, block_n=128,
                           block_k=512, num_cores=8, split_k=4)
    assert split.panels == 4 * base.panels
    assert split.occupancy > base.occupancy
    assert split.hbm_bytes > base.hbm_bytes      # partials round-trip
    assert split.t_pred < base.t_pred


def test_vmem_budget_covers_partials_slab():
    base = K.vmem_bytes(8, 512, 2048)
    split = K.vmem_bytes(8, 512, 2048, split_k=8)
    assert split == base + 8 * 8 * 512 * 4
    # _fit_vmem sees the slab: a triple near the budget clamps under
    # a deep split where it stood unsplit
    from repro.gemm.policy import _fit_vmem
    bm, bn, bk, clamped = _fit_vmem(128, 512, 2048, "float32", None)
    assert not clamped
    assert K.vmem_bytes(bm, bn, bk, split_k=64) > K.VMEM_BUDGET


# --------------------------------------------------------- plan warmup
@pytest.fixture(scope="module")
def packed_engine():
    from repro.models import model_zoo
    from repro.runtime.serve_loop import Engine
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    return cfg, Engine(cfg, model_zoo.build(cfg), max_len=48, packed=True)


def test_warmup_plans_makes_first_tick_hot(packed_engine):
    cfg, eng = packed_engine
    t = eng.warmup_plans(batch_slots=2, prefill_chunk=8, page_size=8,
                         megastep_depth=4)
    assert {"prefill_chunk", "decode_step", "decode_megastep",
            "decode_bucket_plans", "plan_cache"} <= set(t)
    # the decode bucket ladder pre-resolved plans for every packed
    # weight at every DECODE_M_BUCKETS width
    assert t["decode_bucket_plans"] > 0
    misses0 = gemm.plan_cache_info().misses
    from repro.core.packing import PackedWeight
    import jax
    for leaf in jax.tree.leaves(
            eng.params,
            is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(leaf, PackedWeight):
            for b in gemm.DECODE_M_BUCKETS:
                gemm.plan_for_packed(b, leaf, decode=True)
    assert gemm.plan_cache_info().misses == misses0
    misses = gemm.plan_cache_info().misses
    rng = np.random.default_rng(5)
    reqs = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in (5, 11)]
    outs, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=3,
                        prefill_chunk=8, page_size=8, megastep_depth=4)
    assert gemm.plan_cache_info().misses == misses, \
        "first serving tick resolved a plan warmup should have owned"
    refs = [np.asarray(eng.generate(jnp.asarray(r)[None], 3)[0][0])
            for r in reqs]
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_warmup_covers_the_tails_of_a_wide_chunk():
    """Past the bucket ladder the scheduler's tail widths are multiples
    of 128 (``bucket_m``); a 384-row chunk's 200-token tail runs 256
    rows, which warmup must have traced (no plan resolves while
    serving)."""
    from repro.models import model_zoo
    from repro.runtime.serve_loop import Engine
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    eng = Engine(cfg, model_zoo.build(cfg), max_len=640, packed=True)
    t = eng.warmup_plans(batch_slots=1, prefill_chunk=384, page_size=16)
    assert {"prefill_chunk_m128", "prefill_chunk_m256",
            "prefill_chunk"} <= set(t)
    misses = gemm.plan_cache_info().misses
    req = np.arange(1, 585, dtype=np.int32) % cfg.vocab_size
    eng.serve([req], batch_slots=1, max_new_tokens=2, prefill_chunk=384,
              page_size=16)
    assert gemm.plan_cache_info().misses == misses


def test_warmup_rejects_stub_frontends():
    class FakeCfg:
        modality = "image"
    from repro.runtime.serve_loop import Engine
    eng = object.__new__(Engine)
    eng.cfg = FakeCfg()
    with pytest.raises(NotImplementedError):
        Engine.warmup_plans(eng, batch_slots=2)
