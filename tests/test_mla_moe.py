"""DeepSeek-V2-style block on the serving path: YaRN at the published
values, the expert layer's share of an expert-parallel deployment, its
drop-free routing, the latent page pool and the expert-row counter (CPU,
tiny widths)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model_zoo
from repro.models import moe as M
from repro.runtime import kv_cache as KV
from repro.runtime.serve_loop import Engine

FULL = model_zoo.get_config("deepseek-v2-lite")
SHARE = model_zoo.get_config("deepseek-v2-lite-ep8")


def test_yarn_at_the_published_values():
    """Correction range pairs 10..23 of 32, m = 0.1 * 0.707 * ln 40 + 1,
    the softmax scale 192^-0.5 * m^2, and frequencies that keep the
    fast pairs and divide the slow ones by the factor."""
    assert L.yarn_correction_range(FULL, FULL.qk_rope_dim) == (10, 23)
    m = L.yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(1.26081, abs=1e-5)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert A.mla_scale(FULL) == pytest.approx(192 ** -0.5 * m * m)
    f = np.asarray(L.rope_freqs(FULL, 64))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all((f[11:23] < plain[11:23]) & (f[11:23] > plain[11:23] / 40))


def test_share_config_counts_one_stage_of_the_published_model():
    """The one-chip share at 14 layers: 13 MoE layers of 100.4 M (13.77 M
    of attention, 8 x 8.65 M experts, 17.3 M shared, the router), the
    dense layer's 81.0 M and 419.4 M of embedding and head."""
    cfg = dataclasses.replace(SHARE, num_layers=14)
    assert cfg.held_range == (0, 8) and FULL.held_range == (0, 64)
    assert cfg.param_count() == 13 * 100_405_760 + 81_007_104 + 419_430_400


def _moe_cfg(**kw):
    base = dict(d_model=32, num_experts=16, experts_per_token=6,
                moe_d_ff=16, num_shared_experts=1, compute_dtype="float32")
    base.update(kw)
    return dataclasses.replace(FULL, **base)


def test_shares_add_up_to_the_uncut_layer():
    """16 experts held as 4 ranges of 4: the four shares' outputs, with
    the shared expert counted once, add up to the uncut layer's; each
    share's banks are the uncut banks' rows for its experts."""
    full = _moe_cfg()
    key = jax.random.key(3)
    x = jax.random.normal(jax.random.key(4), (2, 12, 32), jnp.float32)
    p_full = M.moe_params(key, full, jnp.float32)
    y_full, rows_full = M.moe_grouped(p_full, full, x)
    shared = M._shared(p_full, full, x.reshape(-1, 32)).reshape(x.shape)
    total = shared
    rows = []
    for i in range(4):
        cfg = dataclasses.replace(full, held_experts=(4 * i, 4 * i + 4))
        p = M.moe_params(key, cfg, jnp.float32)
        np.testing.assert_array_equal(p["wi_gate"],
                                      p_full["wi_gate"][4 * i:4 * i + 4])
        y, r = M.moe_grouped(p, cfg, x)
        total = total + (y - shared)
        rows.append(r)
    # f32 sums in another order: a few ulps of the layer's outputs
    np.testing.assert_allclose(total, y_full, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(rows), rows_full)
    assert int(rows_full.sum()) == 2 * 12 * 6


def test_a_crowded_expert_drops_no_token():
    """Every token routes to expert 0 (one held expert takes all 24
    rows): each token's output equals what it gets alone, where the
    capacity layer would drop all but its capacity."""
    cfg = _moe_cfg(held_experts=(0, 4))
    p = M.moe_params(jax.random.key(5), cfg, jnp.float32)
    p["router"] = p["router"].at[:, 0].set(50.0)
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, 24, 32)))
    y, rows = M.moe_grouped(p, cfg, x)
    assert int(rows[0]) == 24
    for i in range(24):
        alone, _ = M.moe_grouped(p, cfg, x[:, i:i + 1])
        np.testing.assert_allclose(y[0, i], alone[0, 0], rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b",
                                  "deepseek-v2-lite-ep8"])
def test_the_mode_picks_the_moe_layer(arch):
    """The serving modes run the drop-free grouped layer, the train mode
    the capacity layer with its load-balance loss; with room for every
    token the two compute the same logits, for all experts held as for
    a share."""
    from repro.models import transformer
    cfg = dataclasses.replace(model_zoo.reduced_config(
        model_zoo.get_config(arch)), compute_dtype="float32")
    cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    params = transformer.init_params(cfg, jax.random.key(9))
    toks = jax.random.randint(jax.random.key(10), (2, 12), 0,
                              cfg.vocab_size)
    lg_train, _, aux_train = transformer.forward(cfg, params, toks,
                                                 mode="train")
    lg_serve, _, aux_serve = transformer.forward(cfg, params, toks,
                                                 mode="prefill")
    assert float(aux_train) > 0 and float(aux_serve) == 0
    # f32 throughout; the two layers sum the experts in other orders
    np.testing.assert_allclose(lg_serve, lg_train, rtol=2e-4, atol=2e-4)


def test_a_gqa_moe_config_serves_on_the_paged_pool():
    """qwen3-moe (GQA attention, 8 experts at this size, all held) serves
    through Engine.serve on the grouped layer: the greedy tokens of the
    plain full forward, and one expert-row count per dispatch."""
    from repro.models import transformer
    cfg = dataclasses.replace(model_zoo.reduced_config(
        model_zoo.get_config("qwen3-moe-30b-a3b")), compute_dtype="float32")
    params = model_zoo.build(cfg, 0)
    eng = Engine(cfg, params, max_len=96, packed=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (37, 20, 5)]
    outs, stats = eng.serve(prompts, batch_slots=2, max_new_tokens=4,
                            prefill_chunk=16, page_size=16)
    for p, o in zip(prompts, outs):
        seq = jnp.asarray(np.concatenate([p, o[:-1]])[None])
        lg, _, _ = transformer.forward(cfg, params, seq, mode="prefill")
        np.testing.assert_array_equal(jnp.argmax(lg[0, len(p) - 1:], -1), o)
    assert stats.expert_rows_of("prefill").shape == (
        len(stats.prefill_tick_ms), cfg.num_layers, cfg.num_experts)


def test_rows_past_the_groups_are_never_read(monkeypatch):
    """ragged_dot leaves the rows past the groups' sum unspecified (the
    TPU's kernel need not write them): filled with NaN there, the layer
    gives what it gives with zeros, packed banks and raw alike."""
    cfg = _moe_cfg(held_experts=(0, 4))
    p = M.moe_params(jax.random.key(11), cfg, jnp.float32)
    packed = model_zoo.pack_for_inference(cfg, {"moe": p})["moe"]
    x = jax.random.normal(jax.random.key(12), (1, 10, 32), jnp.float32)
    want = [M.moe_grouped(w, cfg, x)[0] for w in (packed, p)]
    real = jax.lax.ragged_dot

    def unspecified(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(live, out, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", unspecified)
    for w, y in zip((packed, p), want):
        np.testing.assert_array_equal(M.moe_grouped(w, cfg, x)[0], y)


def test_grouped_packs_run_through_the_grouped_plan_arm():
    """pack_for_inference packs the held banks as grouped packs; the
    packed layer resolves grouped plans (groups, K, N, row bucket) and
    computes what the raw banks compute."""
    from repro import gemm
    cfg = _moe_cfg(held_experts=(0, 4))
    p = M.moe_params(jax.random.key(7), cfg, jnp.float32)
    packed = model_zoo.pack_for_inference(cfg, {"moe": p})["moe"]
    gu, wo = packed["wi_gate_up"], packed["wo"]
    assert (gu.groups, gu.k, gu.n_splits) == (4, 32, (16, 16))
    assert (wo.groups, wo.k, wo.n) == (4, 16, 32)
    x = jax.random.normal(jax.random.key(8), (1, 10, 32), jnp.float32)
    y_raw, r_raw = M.moe_grouped(p, cfg, x)
    y_pk, r_pk = M.moe_grouped(packed, cfg, x)
    np.testing.assert_array_equal(r_raw, r_pk)
    np.testing.assert_allclose(y_pk, y_raw, rtol=1e-5, atol=1e-6)
    plan = gemm.plan_for_packed(60, gu, epilogue=gemm.EpilogueSpec(
        glu="silu"))
    assert plan.groups == 4 and plan.m == 60 and plan.n_out == 16
    assert plan is gemm.plan_for_packed(60, gu, epilogue=gemm.EpilogueSpec(
        glu="silu"))


def test_latent_pool_rows_fill_whole_lanes():
    assert KV.leaf_specs_for(SHARE) == {
        "pages_lat": ((640,), jnp.dtype("bfloat16"))}


@pytest.fixture(scope="module")
def tiny():
    cfg = model_zoo.reduced_config(SHARE)
    return cfg, model_zoo.build(cfg, 0)


@pytest.mark.parametrize("form", ["expanded", "absorbed"])
def test_serve_matches_the_full_forward(tiny, form, monkeypatch):
    """Chunked prefill, then decode, through Engine.serve on the paged
    latent pool (blocks of 2 pages, so several key blocks) give the
    greedy tokens of the plain full forward, and the scheduler keeps one
    expert-row count per dispatch."""
    monkeypatch.setattr(A, "MLA_KEY_PAGES", 2)
    cfg, params = tiny
    monkeypatch.setattr(A, "MLA_PREFILL_ABSORBED", form == "absorbed")
    eng = Engine(cfg, params, max_len=128, packed=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (37, 70, 5)]
    outs, stats = eng.serve(prompts, batch_slots=2, max_new_tokens=4,
                            prefill_chunk=16, page_size=16)
    from repro.models import transformer
    agree = []
    for p, o in zip(prompts, outs):
        seq = np.concatenate([p, o[:-1]])
        lg, _, _ = transformer.forward(cfg, params, jnp.asarray(seq[None]),
                                       mode="prefill")
        agree.append(np.asarray(jnp.argmax(lg[0, len(p) - 1:], -1)) == o)
    # bf16 activations: the two attention forms round differently, so a
    # near-tie may flip; all but one of 12 tokens must agree
    assert np.concatenate(agree).sum() >= 11
    pre = stats.expert_rows_of("prefill")
    lo, hi = cfg.held_range
    assert pre.shape == (len(stats.prefill_tick_ms),
                         cfg.num_layers - cfg.first_k_dense, hi - lo)
    assert stats.expert_rows_of("decode").shape[0] == stats.decode_dispatches


def test_expert_rows_are_published(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, max_len=64, packed=True)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 20).astype(np.int32)]
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        _, stats = eng.serve(prompts, batch_slots=1, max_new_tokens=3,
                             prefill_chunk=16, page_size=16)
    snap = reg.snapshot()
    total = stats.expert_rows_of().sum()
    assert total > 0
    assert sum(snap["serve_expert_rows"]["series"].values()) == total
    (load,) = snap["serve_expert_load_max_over_mean"]["series"].values()
    assert load >= 1.0


def test_megastep_counts_rows_over_its_ticks(tiny):
    """A decode megastep serves the tokens per-tick decode serves, and
    its one dispatch counts the rows of all its ticks (every slot's row,
    decoding or masked, as the grouped GEMMs compute them)."""
    cfg, params = tiny
    eng = Engine(cfg, params, max_len=64, packed=True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 21)]
    kw = dict(batch_slots=2, max_new_tokens=5, prefill_chunk=16,
              page_size=16)
    ref, s1 = eng.serve(prompts, **kw)
    outs, s3 = eng.serve(prompts, megastep_depth=3, **kw)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)
    dec1, dec3 = s1.expert_rows_of("decode"), s3.expert_rows_of("decode")
    assert len(dec3) == s3.decode_dispatches < len(dec1)
    k = cfg.experts_per_token
    assert dec1.sum(-1).max() <= 2 * k
    assert 0 < dec3.sum(-1).max() <= 2 * k * 3
