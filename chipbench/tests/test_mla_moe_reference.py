"""The DeepSeek-V2 share on the serving path against its plain reference
(``references/mla_moe_block.py``), at tiny widths on the CPU, and the
program's configuration against the published keys of the benchmark's."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct, spec
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model_zoo, transformer
from repro.runtime import kv_cache as KV
from repro.runtime.serve_loop import Engine

REF = spec.load_reference("mla_moe_block")
CONFIG = os.path.join(spec.BENCH_DIR, "configs", "deepseek-v2-lite-14l.json")
SEED = 2**31 + 77

# tiny widths, every mechanism kept: 1 dense + 2 MoE layers, 4 of 16
# experts held, top-6, YaRN over 64 original positions
TINY_KEYS = dict(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, moe_intermediate_size=32,
                 n_routed_experts=4, router_experts=16)
TINY_SIZES = dict(num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
                  d_ff=128, vocab_size=256)


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _tiny():
    config = dict(_published(), **TINY_KEYS)
    config["rope_scaling"] = dict(config["rope_scaling"],
                                  original_max_position_embeddings=64)
    sizes = {spec.HF_TO_PROGRAM[k]: v for k, v in config.items()
             if k in spec.HF_TO_PROGRAM}
    sizes.update(TINY_SIZES)
    cfg = dataclasses.replace(
        model_zoo.get_config(config["program_arch"]), **sizes, head_dim=24,
        kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        moe_d_ff=32, num_experts=16, held_experts=(0, 4),
        yarn_original_max_pos=64)
    return config, sizes, cfg


def test_program_config_agrees_with_every_published_key():
    """deepseek-v2-lite-ep8 states each published key of the benchmark's
    configuration (the held count and depth as the file holds them)."""
    c = _published()
    cfg = dataclasses.replace(model_zoo.get_config(c["program_arch"]),
                              num_layers=c["num_hidden_layers"])
    lo, hi = cfg.held_range
    rs = c["rope_scaling"]
    got = {
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank or None,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts": hi - lo, "router_experts": cfg.num_experts,
        "n_shared_experts": cfg.num_shared_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "first_k_dense_replace": cfg.first_k_dense,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "hidden_act": cfg.act,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_scaling": {
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "factor": cfg.yarn_factor, "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "type": "yarn"},
    }
    assert {k: c[k] for k in got} == got
    assert (lo, c["published"]["n_routed_experts"]) == (0, cfg.num_experts)
    # what the program runs that the file does not list as a key
    assert (c["scoring_func"], c["topk_method"], c["moe_layer_freq"],
            c["n_group"], c["topk_group"], c["attention_bias"]) == (
        "softmax", "greedy", 1, 1, 1, False)
    assert cfg.attention_kind == "mla" and cfg.kv_latent_norm


def test_reference_yarn_is_the_programs():
    c = _published()
    rs = c["rope_scaling"]
    freq, rng = REF.yarn_freqs(64, c["rope_theta"], rs["factor"],
                               rs["original_max_position_embeddings"],
                               rs["beta_fast"], rs["beta_slow"])
    cfg = model_zoo.get_config(c["program_arch"])
    assert rng == (10, 23)
    np.testing.assert_allclose(freq, L.rope_freqs(cfg, 64), rtol=1e-6)
    consts = REF.block_consts(c, {"d_model": 2048, "num_heads": 16,
                                  "d_ff": 10944, "norm_eps": 1e-6,
                                  "rope_theta": 10000})
    assert consts["scale"] == pytest.approx(A.mla_scale(cfg))


def _ref_logits(config, sizes, seqs, rows):
    with jax.default_matmul_precision("highest"):
        return [np.asarray(x) for x in REF.logits_for(
            config, sizes, SEED, seqs, rows)]


@pytest.mark.parametrize("form", ["expanded", "absorbed"])
def test_paged_steps_match_the_reference(form, monkeypatch):
    """Prefill in 16-token chunks, then decode, on the paged latent pool
    (blocks of 2 pages: several key blocks), give the reference's logits
    at every chunk's last row and every decoded row."""
    monkeypatch.setattr(A, "MLA_KEY_PAGES", 2)
    config, sizes, cfg = _tiny()
    monkeypatch.setattr(A, "MLA_PREFILL_ABSORBED", form == "absorbed")
    params = transformer.init_params(cfg, jax.random.key(SEED))
    params = model_zoo.pack_for_inference(cfg, params)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size, 75).astype(np.int32)
    n_prompt, ps, chunk = 69, 16, 16
    kv = KV.PagedKVCache(num_layers=cfg.num_layers, num_slots=1, max_len=96,
                         page_size=ps, leaf_specs=KV.leaf_specs_for(cfg))
    got, rows = [], []
    for start in range(0, n_prompt, chunk):
        end = min(start + chunk, n_prompt)
        kv.alloc(0, end)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :end - start] = seq[start:end]
        cache = {"layers": kv.pages, "page_table": kv.table_device(),
                 "lens": kv.lens_device()}
        lg, cache = transformer.prefill_chunk(
            cfg, params, cache, jnp.asarray(toks), page_size=ps,
            logit_index=jnp.asarray(end - start - 1, jnp.int32))
        kv.pages = cache["layers"]
        kv.lens[0] = end
        got.append(np.asarray(lg[0], np.float32))
        rows.append(end - 1)
    for pos in range(n_prompt, len(seq)):
        kv.alloc(0, pos + 1)
        cache = {"layers": kv.pages, "page_table": kv.table_device(),
                 "lens": kv.lens_device(),
                 "write_mask": jnp.ones((1,), bool)}
        lg, cache = transformer.paged_decode_step(
            cfg, params, cache, jnp.asarray(seq[pos:pos + 1][None]),
            page_size=ps)
        kv.pages = cache["layers"]
        kv.lens[0] = pos + 1
        got.append(np.asarray(lg[0], np.float32))
        rows.append(pos)
    (ref,) = _ref_logits(config, sizes, [seq], [np.asarray(rows)])
    got = np.stack(got)
    # both hold activations, the latent and the expanded keys in bf16 and
    # round at the same points, but sum in other orders (and the absorbed
    # form multiplies through W_uk before, not after, rounding): logits
    # of magnitude ~1 agree to a few bf16 steps, and the argmax holds
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 0.03 * scale
    assert (got.argmax(-1) == ref.argmax(-1)).mean() >= 0.9


def test_engine_serve_is_correct_against_the_reference():
    """Engine.serve's greedy tokens, compared by the benchmark's own
    check: every served token is the reference's first choice or within
    a bf16 rounding of it."""
    config, sizes, cfg = _tiny()
    params = transformer.init_params(cfg, jax.random.key(SEED))
    eng = Engine(cfg, params, max_len=128, packed=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (40, 90, 17)]
    outs, _ = eng.serve(prompts, batch_slots=2, max_new_tokens=6,
                        prefill_chunk=32, page_size=16)
    served = dict(enumerate(outs))
    seqs, rows, toks = correct.sequences(range(3), dict(enumerate(prompts)),
                                         served)
    ref = _ref_logits(config, sizes, seqs, rows)
    g = np.concatenate([correct.gaps(lg, t) for lg, t in zip(ref, toks)])
    assert g.size == 18
    # a bf16 step of logits near 1 is 2^-8: the program picks the
    # reference's best or a token within a few such steps of it
    assert correct.summary(g)["mean_gap"] <= 0.01
