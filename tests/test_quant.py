"""Quantized pre-pack subsystem tests: format laws (quantize/dequantize
bounds, 2-bit pack/unpack, shape laws over odd dims / padding tails /
stacked weights), the dequant-fused kernel's bitwise contract vs the
blocked dequant oracle, epilogue/glu composition, plan/policy/backends
integration, the error-ledger tolerance gate, mixed-precision model
packing, and quantized serve == generate parity.

The round-trip/shape property test runs under hypothesis when installed
and falls back to a deterministic seeded sweep otherwise (so the skip
budget of a bare container does not grow)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import gemm as G
from repro.core import bitexact, packing
from repro.kernels import ref
from repro.quant import formats as F
from repro.quant import ledger

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

RNG = np.random.default_rng(23)


def _rand(shape, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32)
                       * scale)


@pytest.fixture(autouse=True)
def _fresh_cache():
    G.plan_cache_clear()
    yield
    G.plan_cache_clear()


# -------------------------------------------------------- format laws
def _roundtrip_laws(k, n, seed, fmt, stacked=False):
    """The quantize -> dequantize round-trip and shape laws one (k, n,
    seed) instance must satisfy (hypothesis body / fallback sweep)."""
    r = np.random.default_rng(seed)
    shape = (2, k, n) if stacked else (k, n)
    w = jnp.asarray(r.standard_normal(shape).astype(np.float32))
    q, s = F.quantize(w, fmt)
    kg = -(-k // F.GROUP_K)
    assert q.shape == shape and q.dtype == jnp.int8
    assert s.shape == shape[:-2] + (kg, n)
    deq = np.asarray(q.astype(jnp.float32)
                     * F.expand_scales(s, k))
    err = np.abs(deq - np.asarray(w))
    s_row = np.asarray(F.expand_scales(s, k))
    if fmt == "int8":
        assert np.max(np.abs(np.asarray(q))) <= 127
        # per-element bound: half its group's quantization step
        assert np.all(err <= 0.5 * s_row + 1e-6)
    else:
        codes = np.asarray(q)
        assert set(np.unique(codes)) <= {-1, 0, 1}
        # sparse-aware split: zeroed weights are the sub-threshold ones
        packed = F.pack_ternary_codes(
            jnp.asarray(np.pad(codes, [(0, 0)] * (codes.ndim - 2)
                               + [(0, (-k) % 4), (0, 0)])))
        unpacked = np.asarray(F.unpack_ternary_codes(packed))[..., :k, :]
        np.testing.assert_array_equal(unpacked, codes.astype(np.float32))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(k=st.integers(1, 300), n=st.integers(1, 100),
           seed=st.integers(0, 2**31 - 1),
           fmt=st.sampled_from(F.FORMATS),
           stacked=st.booleans())
    def test_quant_roundtrip_property(k, n, seed, fmt, stacked):
        _roundtrip_laws(k, n, seed, fmt, stacked)
else:
    def test_quant_roundtrip_property():
        # deterministic sweep: odd dims, group tails, stacked weights
        cases = [(1, 1), (3, 7), (127, 5), (128, 64), (129, 31),
                 (255, 130), (300, 200), (257, 3)]
        for i, (k, n) in enumerate(cases):
            for fmt in F.FORMATS:
                _roundtrip_laws(k, n, 1000 + i, fmt,
                                stacked=(i % 2 == 0))


@pytest.mark.parametrize("fmt", F.FORMATS)
def test_quantize_pack_shape_laws_odd_dims(fmt):
    """Pack-level shape laws: odd K/N pad to block multiples, scales pad
    to whole groups, padded region dequantizes to exact zero, logical
    dims are preserved."""
    w = _rand((130, 70), 0.02)
    qpw = packing.pack(w, block_n=128, block_k=128, quant=fmt)
    assert (qpw.k, qpw.n) == (130, 70)
    assert qpw.k_pad == 256 and qpw.n_pad == 128
    krows = 64 if fmt == "ternary" else 256
    assert qpw.data.shape == (krows, 128)
    assert qpw.scales.shape == (256 // F.GROUP_K, 128)
    deq = np.asarray(F.dequantize(qpw))
    assert deq.shape == (256, 128)
    assert np.all(deq[130:] == 0) and np.all(deq[:, 70:] == 0)


@pytest.mark.parametrize("fmt", F.FORMATS)
def test_quantize_pack_stacked_and_fused(fmt):
    """Stacked [L, K, N] packs keep the leading dim; fused packs keep
    the static split map with per-part column padding.

    Each part's codes and scales, sliced from the fused pack by its
    split map, equal the single pack's bitwise.  The fused output is
    bitwise the single pack's on the panel kernel (``interpret``), which
    runs the same blocked reduction for every column tile; XLA's dot
    promises no bitwise agreement across widths (512 columns against
    192 or 64), so the ``xla`` outputs are held to rtol 1e-6 with an
    atol of 1e-6 for the elements near zero (outputs are O(0.1-1); the
    reordered sums differ by a few float32 ulps)."""
    w3 = _rand((3, 250, 130), 0.02)
    qpw = F.quantize_pack(w3, fmt, block_n=128, block_k=128)
    assert qpw.data.shape[0] == 3 and qpw.scales.shape[0] == 3
    assert (qpw.k, qpw.n) == (250, 130)
    parts = [_rand((256, wn), 0.02) for wn in (192, 64, 64)]
    qf = packing.pack_fused(parts, block_n=128, block_k=128, quant=fmt)
    assert qf.n_splits == (192, 64, 64)
    assert qf.n_pad == 512                     # 256 + 128 + 128
    singles = [packing.pack(part, block_n=128, block_k=128, quant=fmt)
               for part in parts]
    off = 0
    for ni, q1 in zip(qf.n_splits, singles):
        for fused_arr, single_arr in ((qf.data, q1.data),
                                      (qf.scales, q1.scales)):
            bitexact.assert_bit_identical(
                np.asarray(fused_arr)[..., off:off + ni],
                np.asarray(single_arr)[..., :ni])
        off += -(-ni // qf.block_n) * qf.block_n
    x = _rand((8, 256))
    for backend in ("interpret", "xla"):
        p = G.plan_for_packed(8, qf, backend=backend)
        outs = G.split_fused(p, G.execute(p, x, qf))
        for out, q1 in zip(outs, singles):
            p1 = G.plan_for_packed(8, q1, backend=backend)
            ref_out = np.asarray(G.execute(p1, x, q1))
            if backend == "interpret":
                bitexact.assert_bit_identical(np.asarray(out), ref_out)
            else:
                np.testing.assert_allclose(np.asarray(out), ref_out,
                                           rtol=1e-6, atol=1e-6)


# ------------------------------------------- kernel bitwise contract
@pytest.mark.parametrize("fmt", F.FORMATS)
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_quant_execute_vs_blocked_dequant_oracle(fmt, backend):
    """THE structural contract: the dequant-fused path is bit-identical
    (interpret) / allclose (xla) to the blocked oracle over the SAME
    dequantized panels."""
    m, k, n = 16, 300, 200
    w, x = _rand((k, n), 0.02), _rand((m, k))
    qpw = packing.pack(w, block_n=128, block_k=128, quant=fmt)
    p = G.plan_for_packed(m, qpw, backend=backend)
    y = G.execute(p, x, qpw)
    deq = F.dequantize(qpw)
    xp = jnp.pad(x, ((0, 0), (0, qpw.k_pad - k)))
    if backend == "interpret":
        xp = jnp.pad(xp, ((0, p.m_pad - m), (0, 0)))
        oracle = ref.gemm_blocked(xp, deq, p.block_k)[:m, :n]
        bitexact.assert_bit_identical(np.asarray(y), np.asarray(oracle))
    else:
        oracle = jnp.dot(xp, deq)[:m, :n]
        np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
    assert G.validate_plan(p)


QEPI = [
    G.EpilogueSpec(bias=True),
    G.EpilogueSpec(act="silu"),
    G.EpilogueSpec(softcap=30.0),
    G.EpilogueSpec(bias=True, act="gelu", residual=True),
    G.EpilogueSpec(glu="silu"),
    G.EpilogueSpec(glu="gelu", bias=True, residual=True),
]


def _epi_id(s):
    parts = [k for k, v in (("bias", s.bias), ("res", s.residual)) if v]
    if s.act:
        parts.insert(0, s.act)
    if s.glu:
        parts.insert(0, f"glu-{s.glu}")
    if s.softcap:
        parts.append("softcap")
    return "+".join(parts)


@pytest.mark.parametrize("fmt", F.FORMATS)
@pytest.mark.parametrize("spec", QEPI, ids=_epi_id)
def test_quant_epilogue_bitexact_vs_unfused_sequence(fmt, spec):
    """EpilogueSpec composes with the dequant-fused kernel: fused-quant
    is bit-identical to the unfused quant execute -> jnp ops sequence
    (glu two-accumulator variant included)."""
    m, k = 32, 256
    n = 512 if spec.glu else 256
    if spec.glu:
        pw = packing.pack_fused([_rand((k, n // 2), 0.02),
                                 _rand((k, n // 2), 0.02)],
                                block_n=128, block_k=128, quant=fmt)
    else:
        pw = packing.pack(_rand((k, n), 0.02), block_n=128, block_k=128,
                          quant=fmt)
    x = _rand((m, k))
    kw = dict(backend="interpret")
    base = G.plan_for_packed(m, pw, **kw)
    p = G.plan_for_packed(m, pw, epilogue=spec, **kw)
    assert G.validate_plan(p)
    bias = None
    if spec.bias:
        full = _rand((n,))
        # a fused pack takes one bias per part; the unfused reference
        # epilogue takes the concatenated row
        bias = ([full[:n // 2], full[n // 2:]] if spec.glu else full)
    bias_ref = jnp.concatenate(bias) if isinstance(bias, list) else bias
    res = _rand((m, p.n_out)) if spec.residual else None

    @jax.jit
    def fused(x, pw):
        return G.execute(p, x, pw, bias=bias, residual=res)

    @jax.jit
    def unfused(x, pw):
        acc = G.execute(base, x, pw, out_dtype=jnp.float32)
        return G.apply_epilogue(acc, spec, bias=bias_ref,
                                residual=res).astype(jnp.float32)

    bitexact.assert_bit_identical(np.asarray(fused(x, pw)),
                                  np.asarray(unfused(x, pw)))


# ---------------------------------------------- plan / policy / backends
def test_weight_format_is_plan_keyed_and_prepack_only():
    a = G.plan(128, 512, 256)
    b = G.plan(128, 512, 256, weight_format="int8")
    c = G.plan(128, 512, 256, weight_format="ternary")
    assert len({a, b, c}) == 3 and G.plan_cache_info().misses == 3
    assert b.quantized and b.pack == G.PACK_PREPACKED
    assert a.weight_format == "fp32" and not a.quantized
    assert "weight_format=int8" in b.describe()
    with pytest.raises(ValueError):
        G.plan(128, 512, 256, weight_format="int8", pack=G.PACK_PERCALL)
    with pytest.raises(Exception):
        G.plan(128, 512, 256, weight_format="fp8")     # unknown format


def test_quant_vmem_fit_admits_wider_blocks():
    """int8 streams 4x and ternary 16x fewer weight bytes per tile, so a
    block triple that clamps at fp32 stands at reduced precision."""
    from repro.kernels.panel_gemm import VMEM_BUDGET, vmem_bytes
    bm, bn, bk = 128, 2048, 2048
    assert vmem_bytes(bm, bn, bk) > VMEM_BUDGET
    assert vmem_bytes(bm, bn, bk, weight_format="ternary") < \
        vmem_bytes(bm, bn, bk, weight_format="int8") < \
        vmem_bytes(bm, bn, bk)
    pf = G.plan(128, 4096, 8192, block_n=bn, block_k=bk)
    pq = G.plan(128, 4096, 8192, block_n=bn, block_k=bk,
                weight_format="ternary")
    assert pf.vmem_clamped
    assert (pq.block_n, pq.block_k) == (bn, bk) and not pq.vmem_clamped


def test_execute_mismatch_errors():
    w = _rand((256, 128), 0.02)
    qpw = packing.pack(w, block_n=128, block_k=128, quant="int8")
    pw = packing.pack(w, block_n=128, block_k=128)
    x = _rand((8, 256))
    pq = G.plan_for_packed(8, qpw)
    pf = G.plan_for_packed(8, pw)
    with pytest.raises(G.PlanMismatchError):
        G.execute(pq, x, pw)            # quant plan, fp32 pack
    with pytest.raises(G.PlanMismatchError):
        G.execute(pf, x, qpw)           # fp32 plan, quant pack
    with pytest.raises(G.PlanMismatchError):
        G.execute(pq, x, w)             # quant plan, raw weight


def test_custom_backend_without_run_quant_rejects_quant_plans():
    def run(x_p, w_p, *, block_m, block_n, block_k, out_dtype):
        return jnp.dot(x_p, w_p).astype(out_dtype or x_p.dtype)

    G.register_backend("test-noquant", run)
    try:
        w = _rand((256, 128), 0.02)
        qpw = packing.pack(w, block_n=128, block_k=128, quant="int8")
        p = G.plan_for_packed(8, qpw, backend="test-noquant")
        with pytest.raises(G.PlanMismatchError, match="run_quant"):
            G.execute(p, _rand((8, 256)), qpw)
    finally:
        G.unregister_backend("test-noquant")


# --------------------------------------------------------- error ledger
def test_ledger_records_and_enforces_at_pack_time(monkeypatch):
    ledger.clear()
    w = _rand((256, 192), 0.02)
    qpw = packing.pack(w, block_n=128, block_k=128, quant="int8")
    ent = ledger.lookup(192, 256, "int8")
    assert ent is not None and ent.within_tol
    assert ent.max_rel <= ledger.TOLERANCES["int8"]
    assert ent.max_abs > 0                      # real quantization error
    row = ent.row()
    assert row["within_tol"] and row["format"] == "int8"
    # enforcement: an impossible tolerance makes the SAME pack raise
    monkeypatch.setitem(ledger.TOLERANCES, "int8", 1e-12)
    with pytest.raises(ledger.QuantToleranceError):
        packing.pack(w, block_n=128, block_k=128, quant="int8")


def test_validate_plan_rejects_over_tolerance_ledger_entry():
    """The acceptance gate: a quantized plan whose ledger entry exceeds
    tolerance is REJECTED by validate_plan; within tolerance passes."""
    ledger.clear()
    n, k = 320, 128
    p = G.plan(8, n, k, weight_format="int8")
    ledger.record(ledger.LedgerEntry(n=n, k=k, fmt="int8", max_abs=1.0,
                                     max_rel=0.5, tol=1e-2, probe_m=64))
    assert not G.validate_plan(p)
    ledger.record(ledger.LedgerEntry(n=n, k=k, fmt="int8", max_abs=1e-4,
                                     max_rel=1e-3, tol=1e-2, probe_m=64))
    assert G.validate_plan(p)
    ledger.clear()


def test_ledger_tolerances_match_contract():
    assert ledger.TOLERANCES["int8"] <= 1e-2
    assert "ternary" in ledger.TOLERANCES      # documented ceiling
    with pytest.raises(KeyError):
        ledger.tolerance("fp8")


# ------------------------------------------------- model / serving path
def _smoke_engine(quant, **kw):
    from repro.models import model_zoo
    from repro.runtime.serve_loop import Engine
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    return cfg, Engine(cfg, params, max_len=96, quant=quant, **kw)


def test_pack_for_inference_mixed_precision_tree():
    from repro.models import model_zoo
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    pp = model_zoo.pack_for_inference(cfg, params, quant="int8")
    layers = pp["layers"]
    assert isinstance(layers["attn"]["wqkv"], F.QuantizedPackedWeight)
    assert layers["attn"]["wqkv"].fmt == "int8"
    assert layers["attn"]["wqkv"].n_splits      # fused + quantized
    assert isinstance(layers["ffn"]["w_gate_up"], F.QuantizedPackedWeight)
    # keep_fp32 defaults pin the head (packed fp32) and the embeddings
    assert isinstance(pp["lm_head"], packing.PackedWeight)
    assert not isinstance(pp["lm_head"], F.QuantizedPackedWeight)
    assert not isinstance(pp["embed"], packing.PackedWeight)
    # literal-name pinning keeps that projection fp32
    pp2 = model_zoo.pack_for_inference(
        cfg, params, quant="int8", keep_fp32=("head", "embed", "wo"))
    assert not isinstance(pp2["layers"]["attn"]["wo"],
                          F.QuantizedPackedWeight)


@pytest.mark.parametrize("quant", ["int8", "ternary"])
def test_quant_engine_serve_matches_generate(quant):
    """Acceptance: pack_for_inference(quant=...) serves through
    Engine.serve with parity to one-shot quantized generate."""
    cfg, eng = _smoke_engine(quant)
    rng = np.random.default_rng(5)
    reqs = [rng.integers(0, cfg.vocab_size, int(ln)).astype(np.int32)
            for ln in (7, 12, 4)]
    mns = [4, 3, 5]
    outs, sstats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns)
    assert sstats.quant == quant
    assert sstats.plan_cache is not None
    for req, mn, out in zip(reqs, mns, outs):
        gen, gstats = eng.generate(jnp.asarray(req[None, :]), mn)
        np.testing.assert_array_equal(out, np.asarray(gen)[0])
    assert gstats.quant == quant
    assert gstats.plan_cache.misses > 0


def test_engine_quant_requires_packed():
    from repro.models import model_zoo
    from repro.runtime.serve_loop import Engine
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    with pytest.raises(ValueError):
        Engine(cfg, params, packed=False, quant="int8")


# ----------------------------------------------------- vmem warn satellite
def test_vmem_clamp_warns_once_naming_plan_key():
    with pytest.warns(RuntimeWarning, match="VMEM"):
        p = G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
    assert p.vmem_clamped
    assert G.vmem_clamped_count() >= 1
    # one-time per plan key: the second resolution stays silent
    G.plan_cache_clear()            # drop the plan, keep re-resolving
    import warnings as _w
    from repro.gemm import policy as pol
    pol._vmem_warned.add((128, 4096, 8192, "float32", "xla", "fp32"))
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        G.plan(128, 4096, 8192, block_n=2048, block_k=4096)
