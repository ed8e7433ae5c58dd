"""Sparse-ternary fast-path gates: the compressed zero-group layout
(occupancy bitmap + dense-packed survivor groups + group-offset index),
its exact round-trip law, the sparse group-walk kernel's bitwise
contract vs the dense ternary kernel and the blocked oracle, the
density-bucketed policy arm (plan keys, store round-trip, split-K
rejection, VMEM budget), roofline honesty, ledger density columns, and
serve == generate parity on a group-sparse ternary engine.

The round-trip property runs under hypothesis when installed and falls
back to a deterministic seeded sweep otherwise (same discipline as
test_quant.py)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import gemm as G
from repro.core import bitexact, packing
from repro.gemm.execute import PlanMismatchError, execute
from repro.kernels import panel_gemm as K
from repro.quant import formats as F
from repro.quant import kernels as QK
from repro.quant import ledger

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

GK = F.GROUP_K
RNG = np.random.default_rng(31)


@pytest.fixture(autouse=True)
def _fresh_cache():
    G.plan_cache_clear()
    yield
    G.plan_cache_clear()


def _group_sparse(k, n, zero_groups, seed=0, stacked=0, scale=0.02):
    """A weight with the given whole GROUP_K K-groups zeroed (per layer
    when stacked): the construction every gate in this file runs on."""
    r = np.random.default_rng(seed)
    shape = (stacked, k, n) if stacked else (k, n)
    w = (r.standard_normal(shape) * scale).astype(np.float32)
    for g in zero_groups:
        w[..., g * GK:min((g + 1) * GK, k), :] = 0.0
    return jnp.asarray(w)


# ----------------------------------------------------- round-trip law
def _roundtrip(k, n, seed, stacked, zero_frac):
    r = np.random.default_rng(seed)
    kg = -(-k // GK)
    z = int(zero_frac * kg)
    groups = r.choice(kg, size=min(z, max(0, k // GK)), replace=False) \
        if z else []
    w = _group_sparse(k, n, groups, seed=seed, stacked=stacked)
    qpw = packing.pack(w, quant="ternary", sparse=False)
    spw = F.compress_ternary(qpw)
    back = F.decompress_ternary(spw)
    # bit-for-bit the dense pack the sparse one was built from
    np.testing.assert_array_equal(np.asarray(back.data),
                                  np.asarray(qpw.data))
    np.testing.assert_array_equal(np.asarray(back.scales),
                                  np.asarray(qpw.scales))
    assert (back.n, back.k) == (qpw.n, qpw.k)
    assert 0.0 <= spw.density <= 1.0
    assert spw.density_bucket == F.density_bucket_of(1.0 - spw.density)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(k=st.integers(1, 400), n=st.integers(1, 90),
           seed=st.integers(0, 2**31 - 1),
           stacked=st.sampled_from([0, 2]),
           zero_frac=st.floats(0.0, 1.0))
    def test_sparse_roundtrip_property(k, n, seed, stacked, zero_frac):
        _roundtrip(k, n, seed, stacked, zero_frac)
else:
    def test_sparse_roundtrip_property():
        # deterministic sweep: odd dims, group tails, all-zero,
        # all-dense, stacked layers
        cases = [(1, 1, 0.0), (127, 5, 1.0), (128, 64, 0.5),
                 (129, 31, 0.25), (255, 130, 0.8), (300, 60, 0.4),
                 (384, 17, 1.0), (257, 3, 0.0)]
        for i, (k, n, zf) in enumerate(cases):
            _roundtrip(k, n, 2000 + i, (0, 2)[i % 2], zf)


def test_all_zero_weight_compresses_to_empty_slab():
    w = jnp.zeros((256, 64), jnp.float32)
    spw = packing.pack(w, quant="ternary", sparse=True)
    assert isinstance(spw, F.SparseTernaryPackedWeight)
    assert spw.group_index == ()
    assert spw.density == 0.0 and spw.density_bucket == 9
    y = np.asarray(QK.sparse_ref(jnp.ones((4, 256)), spw))
    assert np.all(y == 0.0)


def test_auto_arm_thresholds_and_forced_layouts():
    # below threshold (dense weight): auto keeps dense
    w_dense = _group_sparse(512, 64, [], seed=3)
    assert not isinstance(packing.pack(w_dense, quant="ternary"),
                          F.SparseTernaryPackedWeight)
    # above threshold: auto compresses
    w_sp = _group_sparse(512, 64, [0, 1], seed=3)
    assert isinstance(packing.pack(w_sp, quant="ternary"),
                      F.SparseTernaryPackedWeight)
    # sparse=False pins dense even above threshold
    assert not isinstance(
        packing.pack(w_sp, quant="ternary", sparse=False),
        F.SparseTernaryPackedWeight)
    # the layout is ternary-only, and sparse= requires quant=
    with pytest.raises(F.QuantFormatError):
        packing.pack(w_sp, quant="int8", sparse=True)
    with pytest.raises(ValueError, match="requires quant='ternary'"):
        packing.pack(w_sp, sparse=True)


# ------------------------------------------------- kernel bitwise gate
@pytest.mark.parametrize("spec,has_bias,has_res", [
    (None, False, False),
    (G.EpilogueSpec(bias=True), True, False),
    (G.EpilogueSpec(act="silu", residual=True), False, True),
    (G.EpilogueSpec(bias=True, glu="silu", residual=True), True, True),
])
def test_sparse_kernel_bitwise_vs_dense_and_oracle(spec, has_bias,
                                                   has_res):
    """The sparse walk == the dense ternary kernel at block_k=GROUP_K
    on the same codes == the blocked oracle, bitwise, across the
    epilogue grid (glu included)."""
    k, n = 384, 128
    glu = spec is not None and spec.glu is not None
    n_log = n * 2 if glu else n
    w = _group_sparse(k, n_log, [1], seed=7)
    if glu:
        qpw = F.quantize_pack_fused([w[:, :n], w[:, n:]], "ternary",
                                    block_n=64, block_k=GK,
                                    sparse=False)
    else:
        qpw = packing.pack(w, block_n=64, block_k=GK, quant="ternary",
                           sparse=False)
    spw = F.compress_ternary(qpw)
    x = jnp.asarray(RNG.standard_normal((16, k)).astype(np.float32))
    bias = jnp.asarray(RNG.standard_normal(n).astype(np.float32)) \
        if has_bias else None
    if glu and bias is not None:
        bias = jnp.concatenate([bias, bias])
    res = jnp.asarray(RNG.standard_normal((16, n)).astype(np.float32)) \
        if has_res else None

    y_sparse = QK.sparse_quant_panel_gemm(
        x, spw.data, spw.scales, bias, res,
        sparse_layout=spw.sparse_layout, block_m=16, block_n=64,
        epilogue=spec, interpret=True)
    y_dense = QK.quant_panel_gemm(
        x, qpw.data, qpw.scales, bias, res, weight_format="ternary",
        block_m=16, block_n=64, block_k=GK, epilogue=spec,
        interpret=True)
    y_ref = QK.sparse_ref(x, spw, epilogue=spec, bias=bias,
                          residual=res)
    bitexact.assert_bit_identical(np.asarray(y_sparse),
                                  np.asarray(y_dense),
                                  "sparse vs dense ternary kernel")
    bitexact.assert_bit_identical(np.asarray(y_sparse),
                                  np.asarray(y_ref),
                                  "sparse kernel vs blocked oracle")


# ------------------------------------------------ plan/policy/execute
def test_plan_key_carries_density_bucket_and_is_stable():
    w = _group_sparse(512, 64, [0, 2], seed=11)
    spw1 = packing.pack(w, quant="ternary", sparse=True)
    spw2 = packing.pack(w, quant="ternary", sparse=True)
    assert spw1.density_bucket == spw2.density_bucket == 5
    p1 = G.plan_for_packed(32, spw1, backend="xla")
    p2 = G.plan_for_packed(32, spw2, backend="xla")
    assert p1 is p2                       # same key -> cached plan hit
    assert p1.density_bucket == 5 and p1.sparse
    # dense pack of the same weight resolves a DIFFERENT plan
    qpw = packing.pack(w, block_n=spw1.block_n, block_k=spw1.block_k,
                       quant="ternary", sparse=False)
    pd = G.plan_for_packed(32, qpw, backend="xla")
    assert pd.density_bucket == -1 and not pd.sparse
    assert pd is not p1


def test_sparse_plan_rejects_split_k_and_non_ternary():
    with pytest.raises(ValueError, match="split_k"):
        G.plan(32, 64, 512, weight_format="ternary", split_k=2,
               density_bucket=5)
    with pytest.raises(ValueError, match="ternary"):
        G.plan(32, 64, 512, weight_format="int8", density_bucket=5)


def test_execute_mismatch_checks():
    w = _group_sparse(512, 64, [0, 2], seed=13)
    spw = packing.pack(w, quant="ternary", sparse=True)
    qpw = packing.pack(w, block_n=spw.block_n, block_k=spw.block_k,
                       quant="ternary", sparse=False)
    x = jnp.asarray(RNG.standard_normal((8, 512)).astype(np.float32))
    sp = G.plan_for_packed(8, spw, backend="xla")
    dp = G.plan_for_packed(8, qpw, backend="xla")
    # matched pairs execute; crossed pairs are PlanMismatch
    execute(sp, x, spw)
    execute(dp, x, qpw)
    with pytest.raises(PlanMismatchError):
        execute(sp, x, qpw)               # sparse plan, dense pack
    with pytest.raises(PlanMismatchError):
        execute(dp, x, spw)               # dense plan, sparse pack


def test_sparse_execute_parity_across_backends():
    w = _group_sparse(640, 96, [0, 3], seed=17)
    spw = packing.pack(w, quant="ternary", sparse=True)
    qpw = packing.pack(w, block_n=spw.block_n, block_k=spw.block_k,
                       quant="ternary", sparse=False)
    x = jnp.asarray(RNG.standard_normal((8, 640)).astype(np.float32))
    ip = G.plan_for_packed(8, spw, backend="interpret")
    y_i = np.asarray(execute(ip, x, spw))
    bitexact.assert_bit_identical(
        y_i, np.asarray(QK.sparse_ref(x, spw))[:, :spw.n],
        "planned sparse interpret vs oracle")
    xp = G.plan_for_packed(8, spw, backend="xla")
    y_x = np.asarray(execute(xp, x, spw))
    y_d = np.asarray(execute(G.plan_for_packed(8, qpw, backend="xla"),
                             x, qpw))
    np.testing.assert_allclose(y_x, y_d, rtol=2e-5, atol=1e-6)


def test_plan_store_roundtrips_density_bucket(tmp_path):
    path = tmp_path / "plans.json"
    store = G.PlanStore(path)
    w = _group_sparse(512, 64, [0, 1], seed=19)
    spw = packing.pack(w, quant="ternary", sparse=True)
    with G.use_plan_store(store):
        p = G.plan_for_packed(16, spw, backend="xla")
    store.save()
    G.plan_cache_clear()
    store2 = G.PlanStore.load(path)
    with G.use_plan_store(store2):
        p2 = G.plan_for_packed(16, spw, backend="xla")
    assert p2.density_bucket == p.density_bucket >= 0
    assert store2.hits >= 1


# -------------------------------------------------- models of the cost
def test_vmem_budget_sparse_monotone_and_group_pinned():
    base = K.vmem_bytes(128, 128, 512, weight_format="ternary")
    s1 = K.vmem_bytes(128, 128, 512, weight_format="ternary",
                      sparse_groups=4, sparse_panels=2)
    s2 = K.vmem_bytes(128, 128, 512, weight_format="ternary",
                      sparse_groups=16, sparse_panels=2)
    # the sparse walk tiles at GROUP_K regardless of block_k, so its
    # x/w/scales tiles are never LARGER than the dense block's; the
    # index slab grows with the occupied-group count
    assert s1 < base + 4 * 4 * (1 + 2) + 1
    assert s2 > s1


def test_roofline_scales_with_density():
    from repro.roofline import V5E, gemm_roofline
    t1 = gemm_roofline(128, 4096, 4096, weight_format="ternary", hw=V5E)
    t3 = gemm_roofline(128, 4096, 4096, weight_format="ternary",
                       weight_density=0.3, hw=V5E)
    assert t3 < t1


def test_sparse_threshold_is_sane():
    th = G.sparse_threshold()
    assert 0.0 < th < 1.0
    # the shipped policy crossover sits at or above the napkin number
    assert F.SPARSE_DENSITY_THRESHOLD >= th


def test_ledger_records_pack_density():
    ledger.clear()
    w = _group_sparse(512, 64, [0, 1], seed=23)
    packing.pack(w, quant="ternary", sparse=True)
    ent = ledger.lookup(64, 512, "ternary")
    assert ent is not None and ent.sparse and ent.density == 0.5
    assert "density" in ent.row()
    ledger.clear()
    packing.pack(w, quant="ternary", sparse=False)
    ent = ledger.lookup(64, 512, "ternary")
    assert ent is not None and not ent.sparse and ent.density == 1.0
    ledger.clear()


# ------------------------------------------------------- serving gate
def test_sparse_engine_serve_matches_generate():
    """A ternary engine whose projections are genuinely group-sparse
    auto-crosses to the compressed layout, serves with parity to
    per-request generate, and surfaces the pack stats."""
    from repro.models import model_zoo
    from repro.runtime.serve_loop import Engine
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    cfg = dataclasses.replace(cfg, d_model=256, d_ff=256,
                              name=cfg.name + "-sparse")
    params = model_zoo.build(cfg)

    def sparsify(path, x):
        if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[-2] >= 256:
            y = np.asarray(x).copy()
            y[..., 0:GK, :] = 0.0
            return jnp.asarray(y)
        return x
    params = jax.tree_util.tree_map_with_path(sparsify, params)
    eng = Engine(cfg, params, max_len=48, packed=True, quant="ternary")
    n_sparse = sum(
        1 for leaf in jax.tree.leaves(
            eng.params,
            is_leaf=lambda v: isinstance(v, F.SparseTernaryPackedWeight))
        if isinstance(leaf, F.SparseTernaryPackedWeight))
    assert n_sparse > 0

    rng = np.random.default_rng(5)
    reqs = [rng.integers(1, cfg.vocab_size, int(ln)).astype(np.int32)
            for ln in (5, 9, 3)]
    mns = [4, 3, 5]
    refs = [np.asarray(eng.generate(jnp.asarray(r)[None], m)[0][0])
            for r, m in zip(reqs, mns)]
    outs, sstats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                             prefill_chunk=8, page_size=8)
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    assert sstats.quant == "ternary"
    assert sstats.quant_sparse_packs == n_sparse
    assert sstats.quant_density is not None and sstats.quant_density < 1.0
    _, gstats = eng.generate(jnp.asarray(reqs[0])[None], 2)
    assert gstats.quant_sparse_packs == n_sparse
    assert gstats.quant_density == sstats.quant_density
