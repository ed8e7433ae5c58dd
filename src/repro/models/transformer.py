"""Decoder stack: one scan-over-layers body serving all four families
(dense / moe / ssm / hybrid) and all three phases (train / prefill /
decode).

Layer heterogeneity (gemma2's local/global alternation) is expressed as
*data*, not structure: a per-layer int32 window array rides the scan as xs
(-1 = full attention), so the stacked-parameter scan body stays uniform and
the HLO stays O(1) in depth — required to keep 61-layer MoE dry-run
compiles tractable on the CPU host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackedWeight, StackedLayer
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# ----------------------------------------------------------------- params
def _init_layer(key, cfg, dtype, moe: bool | None = None):
    """One layer's weights; ``moe=False`` makes a leading dense layer of
    a MoE config (a d_ff-wide SwiGLU where its MoE layers have experts)."""
    moe = cfg.family == "moe" if moe is None else moe
    ks = jax.random.split(key, 8)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if cfg.attention_kind == "gqa":
        p["attn"] = A.gqa_params(ks[0], cfg, dtype)
    elif cfg.attention_kind == "mla":
        p["attn"] = A.mla_params(ks[0], cfg, dtype)
    elif cfg.attention_kind == "parallel_ssm":
        p["attn"] = A.gqa_params(ks[0], cfg, dtype)
        p["mamba"] = S.mamba_params(ks[1], cfg, dtype)
        p["ln_attn_out"] = jnp.ones((cfg.d_model,), dtype)
        p["ln_ssm_out"] = jnp.ones((cfg.d_model,), dtype)
    elif cfg.attention_kind == "none":
        p["mamba"] = S.mamba_params(ks[1], cfg, dtype)
    if cfg.post_norms:
        p["ln1_post"] = jnp.ones((cfg.d_model,), dtype)
        p["ln2_post"] = jnp.ones((cfg.d_model,), dtype)
    if moe:
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["moe"] = M.moe_params(ks[2], cfg, dtype)
    elif cfg.d_ff:
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["ffn"] = {
            "w_gate": L.init_dense(ks[3], (cfg.d_model, cfg.d_ff),
                                   dtype=dtype),
            "w_up": L.init_dense(ks[4], (cfg.d_model, cfg.d_ff),
                                 dtype=dtype),
            "w_down": L.init_dense(ks[5], (cfg.d_ff, cfg.d_model),
                                   dtype=dtype),
        }
    return p


def init_params(cfg, key):
    ks = jax.random.split(key, 4)
    dtype = cfg.pdtype
    params = {}
    if cfg.modality == "text":
        params["embed"] = L.init_dense(ks[0], (cfg.vocab_size, cfg.d_model),
                                       scale=1.0, dtype=dtype)
    # layer l's weights come from split(ks[1], num_layers)[l]; a config's
    # first_k_dense leading layers stack apart (params["dense_layers"])
    # since their FFN differs from the scan's MoE layers
    layer_keys = jax.random.split(ks[1], cfg.num_layers)
    nd = cfg.first_k_dense
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, moe=False))(layer_keys[:nd])
        layer_keys = layer_keys[nd:]
    params["layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not (cfg.tie_embeddings and cfg.modality == "text"):
        params["lm_head"] = L.init_dense(ks[2], (cfg.d_model, cfg.vocab_size),
                                         dtype=dtype)
    return params


def window_pattern(cfg) -> np.ndarray:
    """Per-layer attention window (int32; -1 = full attention)."""
    lyr = cfg.num_layers
    if cfg.local_global_period > 0:
        w = np.full((lyr,), -1, np.int32)
        for i in range(lyr):
            if i % cfg.local_global_period != cfg.local_global_period - 1:
                w[i] = cfg.window
        return w
    if cfg.window is not None:
        return np.full((lyr,), cfg.window, np.int32)
    return np.full((lyr,), -1, np.int32)


# ------------------------------------------------------------------ cache
def init_cache(cfg, batch: int, max_len: int):
    """Pre-allocated decode cache (stacked over layers for the scan)."""
    lyr, dtype = cfg.num_layers, jnp.dtype(cfg.cache_dtype)
    c = {}
    if cfg.attention_kind in ("gqa", "parallel_ssm"):
        t = (min(cfg.window, max_len) if cfg.resolved_cache_kind == "window"
             else max_len)
        c["k"] = jnp.zeros((lyr, batch, t, cfg.num_kv_heads, cfg.head_dim),
                           dtype)
        c["v"] = jnp.zeros_like(c["k"])
        if cfg.resolved_cache_kind == "window":
            c["pos"] = jnp.full((lyr, batch, t), -1, jnp.int32)
    if cfg.attention_kind == "mla":
        c["ckv"] = jnp.zeros((lyr, batch, max_len, cfg.kv_lora_rank), dtype)
        c["krope"] = jnp.zeros((lyr, batch, max_len, cfg.qk_rope_dim), dtype)
    if cfg.attention_kind in ("none", "parallel_ssm"):
        conv_dim = (cfg.ssm_heads * cfg.ssm_head_dim
                    + 2 * cfg.ssm_groups * cfg.ssm_state)
        c["state"] = jnp.zeros(
            (lyr, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
        c["conv"] = jnp.zeros((lyr, batch, cfg.conv_width - 1, conv_dim),
                              dtype)
    return {"layers": c, "index": jnp.zeros((), jnp.int32)}


# ------------------------------------------------------------------ layers
def _layer_forward(cfg, lp, x, *, window_l, positions, cache_l, cache_index,
                   mode, shard_fn=None, page_size=None):
    """One decoder layer.  Returns (x, new_cache_l, aux_loss, rows): rows
    is the grouped MoE layer's count of rows routed to each held expert
    ([held] int32), None for every other layer."""
    aux = jnp.zeros((), jnp.float32)
    rows = None
    new_cache = {}
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps, cfg.norm_plus_one)

    attn_cache = None
    if cache_l is not None and ("pages_k" in cache_l
                                or "pages_lat" in cache_l):
        attn_cache = cache_l          # stacked pools + page_table/lens/layer
    elif cache_l is not None and "k" in cache_l:
        attn_cache = {k: cache_l[k] for k in ("k", "v", "pos")
                      if k in cache_l}
    if cache_l is not None and "ckv" in cache_l:
        attn_cache = {"ckv": cache_l["ckv"], "krope": cache_l["krope"]}
    ssm_cache = None
    if cache_l is not None and "state" in cache_l:
        ssm_cache = {"state": cache_l["state"], "conv": cache_l["conv"]}

    if cfg.attention_kind == "gqa":
        out, nc = A.gqa_attention(lp["attn"], cfg, h, positions=positions,
                                  window=window_l, cache=attn_cache,
                                  cache_index=cache_index,
                                  page_size=page_size)
        if nc:
            new_cache.update(nc)
    elif cfg.attention_kind == "mla":
        out, nc = A.mla_attention(lp["attn"], cfg, h, positions=positions,
                                  cache=attn_cache, cache_index=cache_index,
                                  page_size=page_size, mode=mode)
        if nc:
            new_cache.update(nc)
    elif cfg.attention_kind == "parallel_ssm":
        a_out, nca = A.gqa_attention(lp["attn"], cfg, h, positions=positions,
                                     window=window_l, cache=attn_cache,
                                     cache_index=cache_index)
        s_out, ncs = S.mamba_forward(lp["mamba"], cfg, h, cache=ssm_cache,
                                     mode=mode)
        out = 0.5 * (L.rms_norm(a_out, lp["ln_attn_out"], cfg.norm_eps)
                     + L.rms_norm(s_out, lp["ln_ssm_out"], cfg.norm_eps))
        if nca:
            new_cache.update(nca)
        if ncs:
            new_cache.update(ncs)
    else:                                      # "none": pure SSM mixer
        out, ncs = S.mamba_forward(lp["mamba"], cfg, h, cache=ssm_cache,
                                   mode=mode)
        if ncs:
            new_cache.update(ncs)

    if cfg.post_norms:
        out = L.rms_norm(out, lp["ln1_post"], cfg.norm_eps,
                         cfg.norm_plus_one)
    x = x + out

    if "moe" in lp:
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps, cfg.norm_plus_one)
        if mode == "train":           # capacity dispatch + aux loss
            out2, aux = M.moe_ffn(lp["moe"], cfg, h2,
                                  capacity_factor=cfg.capacity_factor,
                                  shard_fn=shard_fn)
        else:                         # serving: drop-free, grouped GEMMs
            out2, rows = M.moe_grouped(lp["moe"], cfg, h2)
        if cfg.post_norms:
            out2 = L.rms_norm(out2, lp["ln2_post"], cfg.norm_eps,
                              cfg.norm_plus_one)
        x = x + out2
    elif "ffn" in lp:
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps, cfg.norm_plus_one)
        ffn = lp["ffn"]
        if "w_gate_up" in ffn:
            # fused gate+up pack: one pass, glu combine in the store
            # step; in pre-norm blocks the residual add rides the
            # down-projection's epilogue as well
            if cfg.post_norms:
                out2 = L.swiglu_fused(h2, ffn["w_gate_up"], ffn["w_down"],
                                      cfg.act)
                out2 = L.rms_norm(out2, lp["ln2_post"], cfg.norm_eps,
                                  cfg.norm_plus_one)
                x = x + out2
            else:
                x = L.swiglu_fused(h2, ffn["w_gate_up"], ffn["w_down"],
                                   cfg.act, residual=x)
        else:
            out2 = L.swiglu(h2, ffn["w_gate"], ffn["w_up"],
                            ffn["w_down"], cfg.act)
            if cfg.post_norms:
                out2 = L.rms_norm(out2, lp["ln2_post"], cfg.norm_eps,
                                  cfg.norm_plus_one)
            x = x + out2

    return x, (new_cache or None), aux, rows


# ----------------------------------------------------------------- forward
def _split_stack(stack) -> tuple:
    """A stacked layer tree as (xs, packs, treedef): the flat leaves the
    layer scan slices, with None where a pack is handed down whole
    instead, and those packs, with None elsewhere."""
    leaves, treedef = jax.tree.flatten(
        stack, is_leaf=lambda a: isinstance(a, PackedWeight))
    is_pack = [isinstance(a, PackedWeight) for a in leaves]
    xs = [None if f else a for a, f in zip(leaves, is_pack)]
    packs = [a if f else None for a, f in zip(leaves, is_pack)]
    return xs, packs, treedef


def forward(cfg, params, inputs, *, cache=None, mode: str = "train",
            logits_mode: str = "all", shard_fn=None, page_size=None,
            logit_index=None):
    """Run the stack.

    inputs: int tokens [B, S] (text) or embeddings [B, S, d] (stub
    frontends).  mode: train | prefill | decode.  Returns
    (logits, new_cache, aux_loss).  shard_fn: optional activation
    sharding-constraint hook (parallel/sharding.activation_sharder).

    A *paged* cache (dict with ``page_table``/``lens``, see
    runtime/kv_cache) serves the continuous-batching pool: positions are
    per-slot (``lens[b] + i``) and KV reads/writes go through the slot's
    page table, so one static-shape trace serves every mix of slot
    progress.  ``logits_mode="index"`` computes logits for the single row
    ``logit_index`` (traced) — the last *real* row of a padded prefill
    chunk.
    """
    assert mode in ("train", "prefill", "decode")
    assert logits_mode in ("all", "last", "none", "index")
    shard = shard_fn or (lambda x, *names: x)
    if cfg.modality == "text":
        x = L.embed_tokens(params["embed"], inputs).astype(cfg.cdtype)
    else:
        x = inputs.astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.cdtype)
    x = shard(x, "batch", "seq", "d_model")

    paged = cache is not None and "page_table" in cache
    s = x.shape[1]
    if paged:
        if cfg.attention_kind not in ("gqa", "mla"):
            raise NotImplementedError(
                f"paged decode supports GQA and MLA archs; got "
                f"{cfg.attention_kind!r}")
        if page_size is None:
            raise ValueError("paged cache needs page_size")
        cache_index = None
        positions = (cache["lens"][:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None])    # [B, S]
    else:
        cache_index = cache["index"] if cache is not None else 0
        positions = (jnp.arange(s) if mode != "decode"
                     else cache_index + jnp.arange(s))
    w_arr = jnp.asarray(window_pattern(cfg))

    cache_layers = cache["layers"] if cache is not None else None
    has_cache = cache_layers is not None
    # Slot bookkeeping rides OUTSIDE the per-layer subtree: every layer
    # sees the same page_table/lens/write_mask, only the pages differ.
    paged_extra = None
    if paged:
        paged_extra = {k: cache[k] for k in
                       ("page_table", "lens", "write_mask") if k in cache}

    # Cache rides the scan CARRY and is updated in place per layer.  The
    # xs/ys alternative stacks a fresh copy of the whole cache every layer —
    # XLA materializes the ys buffer per iteration (+2 × cache bytes of
    # HBM traffic per layer, the dominant decode term; §Perf C3).
    # A dense or ring cache takes the layer's slice and writes it back
    # (dynamic_update_index on the stacked buffers, under the KV scopes
    # ``kv_view`` / ``kv_write``).  A paged cache hands the stacked pools
    # and the layer index down instead: the KV path scatters into and
    # gathers from the pool at layer ``li`` in place (runtime/kv_cache),
    # so no layer's whole pool is sliced, copied or written back.
    # The stacked packs go down the same way: they stay out of the scan's
    # xs (whose slice XLA would copy a layer at a time), and each layer
    # hands its GEMMs the whole stack and its index (``StackedLayer``);
    # gemm.execute reads the layer in place or slices it, as the plan's
    # run can.  Norms and raw weights ride xs.
    # A config's leading dense layers run as a scan of their own before
    # the main one; the layer index both hand down counts from the stack's
    # first layer, so every layer addresses its own rows of one pool.
    def scan_layers(carry, stack, windows, first):
        sliced, packs, treedef = _split_stack(stack)

        def body(carry, xs):
            x, aux, cl = carry
            lp, w_l, li = xs
            lp = treedef.unflatten([
                leaf if pw is None else StackedLayer(pw, li - first)
                for leaf, pw in zip(lp, packs)])
            c_l = None
            if cl is not None and paged_extra is not None:
                c_l = {**cl, **paged_extra, "layer": li}
            elif cl is not None:
                with jax.named_scope("kv_view"):
                    c_l = jax.tree.map(
                        lambda buf: jax.lax.dynamic_index_in_dim(
                            buf, li, 0, keepdims=False), cl)
            x, new_c, a, rows = _layer_forward(
                cfg, lp, x, window_l=w_l, positions=positions, cache_l=c_l,
                cache_index=cache_index, mode=mode, shard_fn=shard,
                page_size=page_size)
            if new_c is not None and paged_extra is not None:
                cl = new_c
            elif new_c is not None:
                with jax.named_scope("kv_write"):
                    cl = jax.tree.map(
                        lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                            buf, new.astype(buf.dtype), li, 0), cl, new_c)
            x = shard(x, "batch", "seq", "d_model")
            return (x, aux + a, cl), rows

        if mode == "train" and cfg.remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        n = windows.shape[0]
        return jax.lax.scan(body, carry, (sliced, windows,
                                          jnp.arange(first, first + n)))

    carry = (x, jnp.zeros((), jnp.float32), cache_layers)
    nd = cfg.first_k_dense
    if nd:
        carry, _ = scan_layers(carry, params["dense_layers"], w_arr[:nd], 0)
    (x, aux, new_cache_layers), expert_rows = scan_layers(
        carry, params["layers"], w_arr[nd:], nd)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    if logits_mode == "last":
        x = x[:, -1:]
    elif logits_mode == "index":
        # single-row head, same [B, 1, d] GEMM shape as "last" — keeps
        # the padded-final-chunk logits bit-identical to one-shot prefill
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    head = (params["embed"].T if (cfg.tie_embeddings
                                  and cfg.modality == "text")
            else params["lm_head"])
    logits = None
    if logits_mode != "none":
        with jax.named_scope("lm_head"):
            if isinstance(head, PackedWeight) and cfg.logit_softcap:
                # packed LM head: the tanh softcap runs on the fp32
                # accumulator inside the GEMM's store step — the
                # full-vocab logits tensor is written to HBM exactly
                # once, capped
                logits = L.linear(x, head, softcap=cfg.logit_softcap,
                                  out_dtype=jnp.float32)
            else:
                logits = L.linear(x, head)
                logits = L.softcap(logits.astype(jnp.float32),
                                   cfg.logit_softcap)
        logits = shard(logits, "batch", "seq", "vocab")

    new_cache = None
    if has_cache:
        if paged:
            # lens/page_table are host-owned (the scheduler advances
            # them between steps); pass through unchanged.  A grouped
            # MoE stack adds its rows per held expert per MoE layer
            # ([MoE layers, held] int32): the step's expert-load counter
            new_cache = dict(cache, layers=new_cache_layers)
            if expert_rows is not None:
                new_cache["expert_rows"] = expert_rows
        else:
            new_cache = {"layers": new_cache_layers,
                         "index": cache_index + s}
    return logits, new_cache, aux / cfg.num_layers


def loss_fn(cfg, params, batch, *, shard_fn=None, aux_weight: float = 0.01):
    """Mean next-token CE (+ MoE load-balance aux)."""
    logits, _, aux = forward(cfg, params, batch["inputs"], mode="train",
                             logits_mode="all", shard_fn=shard_fn)
    ce = L.cross_entropy(logits, batch["labels"])
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def prefill(cfg, params, inputs, *, max_len: int, shard_fn=None):
    """Batched prefill: build the cache, return last-token logits."""
    b = inputs.shape[0]
    cache = init_cache(cfg, b, max_len)
    logits, cache, _ = forward(cfg, params, inputs, cache=cache,
                               mode="prefill", logits_mode="last",
                               shard_fn=shard_fn)
    return logits[:, 0], cache


def decode_step(cfg, params, cache, tokens, *, shard_fn=None):
    """One decode step.  tokens: [B, 1] ids or [B, 1, d] embeds."""
    logits, cache, _ = forward(cfg, params, tokens, cache=cache,
                               mode="decode", logits_mode="last",
                               shard_fn=shard_fn)
    return logits[:, 0], cache


# -------------------------------------------- continuous-batching steps
def prefill_chunk(cfg, params, cache, tokens, *, page_size, logit_index,
                  shard_fn=None):
    """One chunked-prefill admission step against a paged cache.

    tokens: [B, C] — a fixed-width chunk of one (or more) prompts, padded
    past the prompt end; the pad rows' KV lands beyond the slot's length
    counter and is either masked or overwritten before it is ever read.
    Returns (logits [B, V] for row ``logit_index``, cache) — callers use
    the logits only on a prompt's final chunk.
    """
    logits, cache, _ = forward(cfg, params, tokens, cache=cache,
                               mode="prefill", logits_mode="index",
                               logit_index=logit_index,
                               page_size=page_size, shard_fn=shard_fn)
    return logits[:, 0], cache


def paged_decode_step(cfg, params, cache, tokens, *, page_size,
                      shard_fn=None):
    """One decode step for the whole slot pool against a paged cache:
    per-slot positions come from ``cache['lens']``; slots outside
    ``cache['write_mask']`` (idle / still prefilling) write nothing and
    their logits are discarded by the scheduler."""
    logits, cache, _ = forward(cfg, params, tokens, cache=cache,
                               mode="decode", logits_mode="last",
                               page_size=page_size, shard_fn=shard_fn)
    return logits[:, 0], cache
