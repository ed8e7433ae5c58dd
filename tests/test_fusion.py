"""Model-level gates for the fused-epilogue / horizontal-fusion subsystem.

The serving-stack analogue of the kernel's bit-exactness discipline: with
fp32 compute, an engine running the fused path (one QKV GEMM, one
glu gate-up GEMM, residual/softcap epilogues) must generate token-for-
token — and logit-for-logit — what the unfused packed engine and the
raw-weight engine generate, across the test archs (gqa, gelu+post-norm+
softcap+window gemma2, MLA).  Plus: the fused pack tree's structure, the
GenStats/ServeStats fusion flag, and serving parity with fusion on.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import gemm
from repro.core.packing import PackedWeight
from repro.models import model_zoo, transformer
from repro.runtime.serve_loop import Engine


def _fp32(name):
    cfg = model_zoo.reduced_config(model_zoo.get_config(name))
    return dataclasses.replace(cfg, compute_dtype="float32")


def _prompts(cfg, b=2, s=12, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s)), jnp.int32)


@pytest.fixture(scope="module")
def stablelm32():
    cfg = _fp32("stablelm-3b")
    return cfg, model_zoo.build(cfg)


# ----------------------------------------------------- engine parity
@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma2-9b",
                                  "deepseek-7b"])
def test_fused_engine_matches_unfused_and_raw_fp32(arch):
    """Greedy generation is bit-identical across fused / unfused-packed /
    raw engines at fp32 (gemma2 covers the gelu glu combine, post-norms,
    attn softcap, and local/global windows)."""
    cfg = _fp32(arch)
    params = model_zoo.build(cfg)
    prompts = _prompts(cfg)
    outs = {}
    for key, kw in (("fused", dict(packed=True, fuse=True)),
                    ("unfused", dict(packed=True, fuse=False)),
                    ("raw", dict(packed=False))):
        eng = Engine(cfg, params, max_len=64, **kw)
        outs[key], stats = eng.generate(prompts, 6)
        if kw.get("packed"):
            assert stats.fused is kw.get("fuse", True)
    np.testing.assert_array_equal(np.asarray(outs["fused"]),
                                  np.asarray(outs["unfused"]))
    np.testing.assert_array_equal(np.asarray(outs["fused"]),
                                  np.asarray(outs["raw"]))


def test_fused_mla_engine_matches_raw_fp32():
    """MLA arch: the fused w_dq/w_dkv/w_kr down-projection pack."""
    cfg = _fp32("deepseek-v3-671b")
    params = model_zoo.build(cfg)
    eng_f = Engine(cfg, params, max_len=32, packed=True, fuse=True)
    eng_r = Engine(cfg, params, max_len=32, packed=False)
    assert "w_dqkr" in eng_f.params["layers"]["attn"]
    prompts = _prompts(cfg, s=8)
    g_f, _ = eng_f.generate(prompts, 4)
    g_r, _ = eng_r.generate(prompts, 4)
    np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_r))


def test_fused_logits_bitexact_fp32(stablelm32):
    """Not just argmax: the full prefill logits are bit-identical."""
    cfg, params = stablelm32
    prompts = _prompts(cfg, s=10, seed=3)
    l_f, _ = Engine(cfg, params, max_len=32, packed=True,
                    fuse=True).prefill(prompts)
    l_r, _ = Engine(cfg, params, max_len=32, packed=False).prefill(prompts)
    assert l_f.dtype == l_r.dtype
    np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_r))


def test_fused_softcap_head_bitexact_fp32():
    """An untied softcap LM head routes the cap through the GEMM's store
    step (packed) — bit-identical to the unfused linear -> softcap."""
    cfg = dataclasses.replace(_fp32("gemma2-9b"), tie_embeddings=False)
    params = model_zoo.build(cfg)
    assert "lm_head" in params and cfg.logit_softcap
    prompts = _prompts(cfg, s=9, seed=5)
    l_f, _ = Engine(cfg, params, max_len=32, packed=True,
                    fuse=True).prefill(prompts)
    l_r, _ = Engine(cfg, params, max_len=32, packed=False).prefill(prompts)
    np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_r))


# ------------------------------------------------------ pack structure
def test_pack_for_inference_fuses_groups(stablelm32):
    cfg, params = stablelm32
    packed = model_zoo.pack_for_inference(cfg, params)
    attn = packed["layers"]["attn"]
    ffn = packed["layers"]["ffn"]
    assert "wqkv" in attn and "wq" not in attn and "wk" not in attn
    assert isinstance(attn["wqkv"], PackedWeight)
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert attn["wqkv"].n_splits == (h * hd, hkv * hd, hkv * hd)
    # stacked per-layer pack: leading L dim rides through
    assert attn["wqkv"].data.ndim == 3
    assert attn["wqkv"].data.shape[0] == cfg.num_layers
    assert "w_gate_up" in ffn and "w_gate" not in ffn
    assert ffn["w_gate_up"].n_splits == (cfg.d_ff, cfg.d_ff)
    # wo / w_down stay single packs
    assert isinstance(attn["wo"], PackedWeight)
    assert not attn["wo"].n_splits


def test_pack_for_inference_no_fusion_escape_hatch(stablelm32):
    cfg, params = stablelm32
    unpacked = model_zoo.pack_for_inference(cfg, params, fuse=False)
    attn = unpacked["layers"]["attn"]
    assert "wq" in attn and "wqkv" not in attn
    assert "w_gate" in unpacked["layers"]["ffn"]


def test_prefill_emits_fewer_gemms_when_fused(stablelm32):
    """The acceptance criterion at HLO level: the fused prefill trace
    contains >= 2 fewer dot ops per transformer block than unfused."""
    cfg, params = stablelm32
    prompts = _prompts(cfg, s=8, seed=7)

    def n_dots(fuse):
        packed = model_zoo.pack_for_inference(cfg, params, fuse=fuse)
        fn = jax.jit(lambda p, t: transformer.prefill(cfg, p, t,
                                                      max_len=16))
        hlo = fn.lower(packed, prompts).as_text()
        return hlo.count("dot_general")

    unfused, fused = n_dots(False), n_dots(True)
    # per (scanned) block: qkv 3->1 and gate+up 2->1 = 3 fewer GEMMs
    assert unfused - fused >= 3, (unfused, fused)


# ---------------------------------------------------- serving with fusion
def test_serve_parity_with_fusion_on(stablelm32):
    """Continuous batching over the fused engine stays bit-identical to
    per-request generate (the test_serving gate, fusion explicitly on),
    and the stats report the fused path."""
    cfg, params = stablelm32
    eng = Engine(cfg, params, max_len=48, packed=True, fuse=True)
    rng = np.random.default_rng(11)
    reqs = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in (5, 17, 8)]
    mns = [6, 3, 5]
    refs = [np.asarray(eng.generate(jnp.asarray(r)[None], m)[0][0])
            for r, m in zip(reqs, mns)]
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=8, page_size=8)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert stats.fused is True


def test_near_budget_pack_survives_residual_epilogue():
    """Review fix: the VMEM footprint budgets bias/residual operand
    headroom unconditionally, so a pack that fits cannot be re-clamped
    below its own blocks when the layer attaches a residual epilogue."""
    from repro.core import packing
    from repro.models import layers as L
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((2048, 2944)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 2048)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((8, 2944)), jnp.float32)
    pw = packing.pack(w, block_n=2944, block_k=512)
    y = L.linear(x, pw, residual=r)        # raised PlanMismatchError
    assert y.shape == (8, 2944)


def test_plan_cache_stays_hot_under_fused_serving(stablelm32):
    cfg, params = stablelm32
    eng = Engine(cfg, params, max_len=48, packed=True, fuse=True)
    rng = np.random.default_rng(13)
    reqs = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in (5, 12, 9)]
    eng.serve(reqs, batch_slots=2, max_new_tokens=4, prefill_chunk=8,
              page_size=8)
    misses = gemm.plan_cache_info().misses
    reqs2 = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
             for l in (7, 3, 14)]
    eng.serve(reqs2, batch_slots=2, max_new_tokens=3, prefill_chunk=8,
              page_size=8)
    assert gemm.plan_cache_info().misses == misses
