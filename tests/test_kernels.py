"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
bit-exactness vs the blocked oracle (the paper's 0e+00 discipline), and
hypothesis property tests on the GEMM invariants."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from repro.core import bitexact
from repro.kernels import ops, ref
from repro.kernels.panel_gemm import (EpilogueSpec, panel_gemm,
                                      panel_gemm_splitk, vmem_bytes,
                                      VMEM_BUDGET)

RNG = np.random.default_rng(42)


def _rand(shape, dtype=np.float32):
    return jnp.asarray(RNG.standard_normal(shape).astype(dtype))


# ---------------------------------------------------------------- panel gemm
@pytest.mark.parametrize("m,n,k", [
    (128, 256, 256), (128, 512, 128), (256, 128, 384),
    (128, 2048 // 4, 2048 // 4),   # scaled QKV class
    (128, 8192 // 16, 2048 // 8),  # scaled FFN1 (N > K)
    (128, 2048 // 8, 8192 // 16),  # scaled FFN2 (K > N)
])
def test_panel_gemm_vs_blocked_oracle_bitexact(m, n, k):
    x, w = _rand((m, k)), _rand((k, n))
    bk = min(128, k)
    y = panel_gemm(x, w, block_m=128, block_n=128, block_k=bk,
                   interpret=True)
    bitexact.assert_bit_identical(
        np.asarray(y), np.asarray(ref.gemm_blocked(x, w, bk)))


@pytest.mark.parametrize("m,n,k", [(64, 96, 200), (128, 130, 256),
                                   (1, 300, 77), (129, 128, 128)])
def test_panel_gemm_unaligned_shapes(m, n, k):
    x, w = _rand((m, k)), _rand((k, n))
    y = ops.gemm(x, w, interpret=True)
    np.testing.assert_allclose(y, ref.gemm_xla(x, w), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_panel_gemm_dtypes(dtype):
    x = _rand((128, 256)).astype(dtype)
    w = _rand((256, 128)).astype(dtype)
    y = panel_gemm(x, w, block_m=128, block_n=128, block_k=128,
                   interpret=True)
    expect = ref.gemm_blocked(x, w, 128)
    assert y.dtype == dtype
    if dtype == jnp.float32:
        bitexact.assert_bit_identical(np.asarray(y), np.asarray(expect))
    else:
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_panel_gemm_kcarry_no_leak_across_tiles():
    """The skip-Z discipline: two output tiles sharing the accumulator
    scratch must not leak partial sums (grid > 1 in both i and j)."""
    x, w = _rand((256, 512)), _rand((512, 256))
    y = panel_gemm(x, w, block_m=128, block_n=128, block_k=128,
                   interpret=True)
    bitexact.assert_bit_identical(
        np.asarray(y), np.asarray(ref.gemm_blocked(x, w, 128)))


def test_vmem_model_deployed_blocks_fit():
    from repro.kernels.panel_gemm import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_M,
                                          DEFAULT_BLOCK_N)
    assert vmem_bytes(DEFAULT_BLOCK_M, DEFAULT_BLOCK_N,
                      DEFAULT_BLOCK_K) <= VMEM_BUDGET


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(1, 64), n=st.integers(1, 64), k=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_gemm_matches_xla_property(m, n, k, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(r.standard_normal((k, n)).astype(np.float32))
    y = ops.gemm(x, w, interpret=True)
    np.testing.assert_allclose(y, ref.gemm_xla(x, w), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_gemm_linearity_property(seed):
    """GEMM invariant: (a x1 + x2) W == a (x1 W) + x2 W (fp32, loose tol)."""
    r = np.random.default_rng(seed)
    x1 = jnp.asarray(r.standard_normal((32, 64)).astype(np.float32))
    x2 = jnp.asarray(r.standard_normal((32, 64)).astype(np.float32))
    w = jnp.asarray(r.standard_normal((64, 32)).astype(np.float32))
    lhs = ops.gemm(2.0 * x1 + x2, w, interpret=True)
    rhs = 2.0 * ops.gemm(x1, w, interpret=True) + ops.gemm(
        x2, w, interpret=True)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("s,t,h,hkv,d", [
    (128, 128, 4, 4, 64), (256, 256, 4, 2, 64), (64, 192, 8, 2, 32),
    (100, 100, 2, 1, 80),
])
def test_flash_attention_vs_ref(s, t, h, hkv, d):
    q = _rand((2, s, h, d))
    k = _rand((2, t, hkv, d))
    v = _rand((2, t, hkv, d))
    o = ops.mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(o, ref.attention(q, k, v, causal=True),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(None, 30.0), (64, None),
                                            (64, 50.0), (17, None)])
def test_flash_attention_window_softcap(window, softcap):
    q, k, v = _rand((1, 256, 4, 64)), _rand((1, 256, 2, 64)), _rand(
        (1, 256, 2, 64))
    o = ops.mha(q, k, v, causal=True, window=window, softcap=softcap,
                interpret=True)
    np.testing.assert_allclose(
        o, ref.attention(q, k, v, causal=True, window=window,
                         softcap=softcap), rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_cache_alignment():
    """Sq < Skv (decode/cache case): positions must align to cache end."""
    q, k, v = _rand((2, 1, 4, 64)), _rand((2, 300, 4, 64)), _rand(
        (2, 300, 4, 64))
    o = ops.mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(o, ref.attention(q, k, v, causal=True),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    q = _rand((1, 128, 2, 64)).astype(dtype)
    k = _rand((1, 128, 2, 64)).astype(dtype)
    v = _rand((1, 128, 2, 64)).astype(dtype)
    o = ops.mha(q, k, v, causal=True, interpret=True)
    o_ref = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------- stacked packs, read in place
STACK_L = 3


@pytest.mark.parametrize("variant", ["plain", "glu", "splitk"])
def test_stacked_kernel_reads_each_layer_bit_identically(variant):
    """The kernel handed the whole stack [L, K, N] and a layer index reads
    that layer's panels: bit-identical, at every index, to the same kernel
    handed the layer's 2-D slice."""
    x, w = _rand((16, 512)), _rand((STACK_L, 512, 256))
    if variant == "splitk":
        run = functools.partial(panel_gemm_splitk, split_k=2, block_m=8,
                                block_n=128, block_k=128, interpret=True)
    else:
        spec = EpilogueSpec(glu="silu") if variant == "glu" else None
        run = functools.partial(panel_gemm, block_m=8, block_n=128,
                                block_k=128, epilogue=spec, interpret=True)
    for li in range(STACK_L):
        bitexact.assert_bit_identical(
            np.asarray(run(x, w, layer=jnp.int32(li))),
            np.asarray(run(x, w[li])))


@pytest.fixture(scope="module")
def first_k_dense():
    """A tiny MLA/MoE config with a leading dense layer, packed."""
    from repro.models import model_zoo
    cfg = model_zoo.reduced_config(
        model_zoo.get_config("deepseek-v2-lite-ep8"))
    assert cfg.first_k_dense
    return cfg, model_zoo.pack_for_inference(cfg, model_zoo.build(cfg, 0))


def _stacked_packs(params, kind):
    """(stack name, path, pack) of every stacked pack of ``kind`` in the
    leading dense stack and the main stack: float32 projection packs,
    quantized packs or grouped expert banks."""
    from repro.core.packing import PackedWeight
    from repro.quant.formats import QuantizedPackedWeight
    out = []
    for stack in ("dense_layers", "layers"):
        if stack not in params:
            continue
        for path, pw in jax.tree_util.tree_flatten_with_path(
                params[stack],
                is_leaf=lambda a: isinstance(a, PackedWeight))[0]:
            if not isinstance(pw, PackedWeight):
                continue
            quant = isinstance(pw, QuantizedPackedWeight)
            if {"fp32": not quant and not pw.groups, "int8": quant,
                    "grouped": bool(pw.groups) and not quant}[kind]:
                out.append((stack, jax.tree_util.keystr(path), pw))
    return out


@pytest.mark.parametrize("kind,backend", [
    ("fp32", "interpret"), ("fp32", "xla"), ("int8", "interpret"),
    ("grouped", "interpret")])
def test_execute_at_a_layer_is_the_sliced_call(first_k_dense, kind,
                                               backend):
    """``gemm.execute(plan, x, stack, layer=li)`` gives, bit for bit, what
    the call on the layer's slice gives, for every pack of the leading
    dense stack and of the main stack, at every layer: float32 packs in
    place (``interpret``) and on ``xla``, which indexes the layer and dots
    as the scan's slice did before; quantized packs and grouped banks,
    whose every array (codes and scales, the bank) execute indexes."""
    from repro import gemm
    from repro.models import model_zoo
    cfg, params = first_k_dense
    if kind == "int8":
        params = model_zoo.pack_for_inference(
            cfg, model_zoo.build(cfg, 0), quant="int8")
    packs = _stacked_packs(params, kind)
    assert packs
    if kind == "fp32":
        assert {s for s, _, _ in packs} == {"dense_layers", "layers"}
    for stack, path, pw in packs:
        x = _rand((6, pw.k)).astype(cfg.cdtype)
        glu = "gate_up" in path and not pw.groups
        p = gemm.plan_for_packed(6, pw, backend=backend,
                                 epilogue=EpilogueSpec(glu="silu") if glu
                                 else None)
        kw = ({"group_sizes": jnp.asarray([2, 0, 3] + [0] * (pw.groups - 3),
                                          jnp.int32)}
              if pw.groups else {})
        at = jax.jit(lambda x, w, li, p=p, kw=kw: gemm.execute(
            p, x, w, layer=li, **kw))
        sliced = jax.jit(lambda x, w, p=p, kw=kw: gemm.execute(
            p, x, w, **kw))
        for li in range(pw.data.shape[0]):
            bitexact.assert_bit_identical(
                np.asarray(at(x, pw, jnp.int32(li))),
                np.asarray(sliced(x, jax.tree.map(lambda a: a[li], pw))),
                f"{stack}{path}[{li}]")


def test_forward_reads_both_stacks_at_their_own_layers(first_k_dense,
                                                       monkeypatch):
    """The layer scans hand each projection the stack and its index
    counted from the stack's first layer: a forward that reads the packs
    in place gives the logits, bit for bit, of one whose scans slice
    every pack (both on the interpreted kernel)."""
    from repro import gemm
    from repro.models import transformer
    cfg, params = first_k_dense
    tokens = jnp.asarray(np.arange(12, dtype=np.int32)[None] % 50)

    def logits():
        with gemm.use_backend("interpret"):
            return np.asarray(transformer.forward(
                cfg, params, tokens, mode="prefill")[0])

    in_place = logits()
    split = transformer._split_stack
    monkeypatch.setattr(transformer, "_split_stack", lambda stack: (
        lambda xs, packs, td: ([p if a is None else a
                                for a, p in zip(xs, packs)],
                               [None] * len(packs), td))(*split(stack)))
    bitexact.assert_bit_identical(in_place, logits())
