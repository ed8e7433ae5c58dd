"""Attention variants: GQA / SWA / local-global / softcap / MLA (absorbed).

The workhorse is ``blocked_attention`` — an online-softmax (flash) attention
in pure JAX with lax.scan over KV chunks.  It is the memory-safe path used
for prefill_32k / train_4k lowering (HLO stays small, no (S, T) scores
materialization) and it accepts *traced* per-layer window / kv_len scalars
so a single scan-over-layers body serves alternating local/global patterns
(gemma2), growing decode caches, and SWA ring caches (explicit per-slot
``k_pos``; softmax is permutation-invariant over key order, so an unordered
ring buffer only needs true positions, not re-sorting).

On TPU the same math runs as the Pallas kernel in kernels/flash_attention.py
(validated against the same oracle); runtime selection mirrors the
gemm backend registry's explicit choice.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models import flash_vjp as _fv

_NEG = -1e30

# §Perf iteration B: route the backward through the flash custom-VJP
# (O(S·D) residuals) instead of reverse-mode through the chunk scan
# (O(S·T) stacked score residuals).  Forward math is identical.
USE_FLASH_VJP = os.environ.get("REPRO_FLASH_VJP", "1") != "0"


def blocked_attention(
    q: jax.Array,                  # [B, S, H, D]
    k: jax.Array,                  # [B, T, Hkv, D]
    v: jax.Array,                  # [B, T, Hkv, Dv]
    *,
    scale: float,
    causal: bool = True,
    window=None,                   # None | int | traced int32 (<=0 => full)
    softcap: float | None = None,
    kv_len=None,                   # traced valid-cache length (default T)
    q_offset=0,                    # traced start position of q row 0:
                                   # scalar, or [B] per-slot offsets
                                   # (continuous-batching decode, where
                                   # every slot sits at its own length)
    k_pos=None,                    # [B, T] explicit key positions (ring
                                   # caches); -1 marks an empty slot
    chunk: int = 512,
) -> jax.Array:
    b, s, h, d = q.shape
    _, t, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    chunk = min(chunk, t)
    pad = (-t) % chunk
    tp = t + pad
    nc = tp // chunk
    # the keys' view padded to whole blocks, and its position mask: the
    # KV path's ``kv_view`` scope, not attention's
    with jax.named_scope("kv_view"):
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            if k_pos is not None:
                k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                                constant_values=-1)
        if k_pos is None:
            kv_len = t if kv_len is None else kv_len
            k_pos = jnp.broadcast_to(jnp.arange(tp)[None], (b, tp))
            k_pos = jnp.where(k_pos < kv_len, k_pos, -1)

    q_off = jnp.asarray(q_offset, jnp.int32)
    if q_off.ndim == 0:
        q_off = jnp.broadcast_to(q_off, (b,))
    q_pos2d = q_off[:, None] + jnp.arange(s, dtype=jnp.int32)[None]  # [B,S]

    if USE_FLASH_VJP:
        q_pos_f = q_pos2d.astype(jnp.float32)
        if window is None:
            window_f = jnp.zeros((), jnp.float32)       # disabled
        else:
            window_f = jnp.asarray(window).astype(jnp.float32)
        return _fv.flash_attention(
            q, k, v, k_pos.astype(jnp.float32), q_pos_f, window_f,
            scale, causal, softcap, chunk)

    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)

    kc = k.reshape(b, nc, chunk, hkv, d).swapaxes(0, 1)     # [nc,B,c,Hkv,D]
    vc = v.reshape(b, nc, chunk, hkv, dv).swapaxes(0, 1)
    pc = k_pos.reshape(b, nc, chunk).swapaxes(0, 1)         # [nc,B,c]

    def step(carry, xs):
        m, l, acc = carry
        k_c, v_c, p_c = xs
        s_blk = jnp.einsum("bskgd,bckd->bkgsc", qg,
                           k_c.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s_blk = softcap * jnp.tanh(s_blk / softcap)
        kp = p_c[:, None, :]                                # [B,1,c]
        qp = q_pos2d[:, :, None]                            # [B,S,1]
        mask = kp >= 0
        if causal:
            mask &= qp >= kp
        if window is not None:
            in_win = (qp - kp) < window
            mask &= in_win if isinstance(window, int) else jnp.logical_or(
                window <= 0, in_win)
        mask_e = mask[:, None, None]                        # [B,1,1,S,c]
        s_blk = jnp.where(mask_e, s_blk, _NEG)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        p = jnp.where(mask_e, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgsc,bckv->bkgsv", p, v_c.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, s, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)     # [B,S,Hkv,G,Dv]
    return out.reshape(b, s, h, dv).astype(q.dtype)


# --------------------------------------------------------------------- GQA
def gqa_params(key, cfg, dtype):
    """Weights for one GQA attention block (flattened 2D for packing)."""
    from repro.models import layers as L
    ks = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": L.init_dense(ks[0], (d, h * hd), dtype=dtype),
        "wk": L.init_dense(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": L.init_dense(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": L.init_dense(ks[3], (h * hd, d), dtype=dtype),
    }


def _update_full_cache(cache, k, v, cache_index, s):
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
    return {"k": ck, "v": cv}, ck, cv, None, cache_index + s, cache_index


def _update_ring_cache(cache, k, v, cache_index, s):
    """SWA ring cache of width W.  Slots hold absolute positions in
    cache['pos'] (-1 = empty); attention masks by position, so slot order
    is irrelevant."""
    w = cache["k"].shape[1]
    b = k.shape[0]
    pos_new = cache_index + jnp.arange(s)
    if s >= w:                      # prefill longer than the window
        k_in, v_in = k[:, -w:], v[:, -w:]
        pos_in = jnp.broadcast_to(pos_new[-w:][None], (b, w))
        ck = k_in.astype(cache["k"].dtype)
        cv = v_in.astype(cache["v"].dtype)
        cp = pos_in
    else:                           # decode (s==1) or short prefill
        slot = cache_index % w
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cp = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.broadcast_to(pos_new[None], (b, s)), slot,
            axis=1)
    new = {"k": ck, "v": cv, "pos": cp}
    return new, ck, cv, cp, None, cache_index


def _update_paged_cache(cache, k, v, page_size):
    """Continuous-batching paged cache (runtime/kv_cache): scatter the new
    tokens of every slot at its own length into the stacked pool at layer
    ``cache['layer']``, then gather the logical-order dense view from it;
    both index the pool in place.  The gathered view has the same length
    and chunk layout as the dense ``[B, max_len]`` cache, and masked
    positions contribute exact zeros, so attention here is bit-identical
    to the dense path — the serving parity gate (tests/test_serving.py)
    rests on this."""
    from repro.runtime import kv_cache as KV
    pt, lens, li = cache["page_table"], cache["lens"], cache["layer"]
    wm = cache.get("write_mask")
    s = k.shape[1]
    pk = KV.paged_update(cache["pages_k"], k, pt, lens, page_size, li,
                         write_mask=wm)
    pv = KV.paged_update(cache["pages_v"], v, pt, lens, page_size, li,
                         write_mask=wm)
    k_d = KV.paged_gather(pk, pt, page_size, li)
    v_d = KV.paged_gather(pv, pt, page_size, li)
    t_view = k_d.shape[1]
    with jax.named_scope("kv_view"):
        if k_d.ndim != k.ndim:
            # a pool of narrow heads holds a token's heads as one row
            # (kv_cache.leaf_specs_for).  Pinned row-major, the view is
            # relaid out once, here; left free, XLA transposes it to make
            # the reshape free and copies every attention block back.
            rows = Layout(major_to_minor=tuple(range(k.ndim)))
            k_d = with_layout_constraint(
                k_d.reshape(k_d.shape[:2] + k.shape[2:]), rows)
            v_d = with_layout_constraint(
                v_d.reshape(v_d.shape[:2] + v.shape[2:]), rows)
        k_pos = jnp.arange(t_view, dtype=jnp.int32)[None, :]
        k_pos = jnp.where(k_pos < lens[:, None] + s, k_pos, -1)
    return {"pages_k": pk, "pages_v": pv}, k_d, v_d, k_pos, None, lens


def gqa_attention(p, cfg, x, *, positions, window=None, cache=None,
                  cache_index=None, page_size=None):
    """GQA attention.  cache: dict(k=[B,T,Hkv,D], v=..., pos=... for ring)
    updated at cache_index, or a paged cache (the stacked pages_k/pages_v
    pools + page_table/lens and the scan's layer index, per-slot
    offsets).  Returns (out, new_cache)."""
    from repro.models import layers as L
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if "wqkv" in p:
        # horizontally fused pack (model_zoo.pack_for_inference): one
        # GEMM pass streams x once and produces all three projections
        q, k, v = L.fused_linear(x, p["wqkv"])
    else:
        q, k, v = (L.linear(x, p["wq"]), L.linear(x, p["wk"]),
                   L.linear(x, p["wv"]))
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    scale = cfg.attn_scale if cfg.attn_scale else hd ** -0.5

    new_cache, k_pos, kv_len, q_offset = None, None, s, 0
    if cache is not None:
        if "pages_k" in cache:
            new_cache, k, v, k_pos, kv_len, q_offset = _update_paged_cache(
                cache, k, v, page_size)
        elif "pos" in cache:
            new_cache, k, v, k_pos, kv_len, q_offset = _update_ring_cache(
                cache, k, v, cache_index, s)
        else:
            new_cache, k, v, k_pos, kv_len, q_offset = _update_full_cache(
                cache, k, v, cache_index, s)

    out = blocked_attention(
        q, k, v, scale=scale, causal=True, window=window,
        softcap=cfg.attn_softcap, kv_len=kv_len, q_offset=q_offset,
        k_pos=k_pos)
    return L.linear(out.reshape(b, s, h * hd), p["wo"]), new_cache


# --------------------------------------------------------------------- MLA
def mla_params(key, cfg, dtype):
    """DeepSeek-V3 Multi-head Latent Attention weights (absorbed layout)."""
    from repro.models import layers as L
    ks = jax.random.split(key, 8)
    d, h = cfg.d_model, cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "w_dq": L.init_dense(ks[0], (d, cfg.q_lora_rank), dtype=dtype),
        "w_uq": L.init_dense(ks[1], (cfg.q_lora_rank, h * (nope + rope_d)),
                             dtype=dtype),
        "w_dkv": L.init_dense(ks[2], (d, cfg.kv_lora_rank), dtype=dtype),
        "w_kr": L.init_dense(ks[3], (d, rope_d), dtype=dtype),
        "w_uk": L.init_dense(ks[4], (cfg.kv_lora_rank, h * nope),
                             dtype=dtype),
        "w_uv": L.init_dense(ks[5], (cfg.kv_lora_rank, h * vd), dtype=dtype),
        "wo": L.init_dense(ks[6], (h * vd, d), dtype=dtype),
    }


def mla_attention(p, cfg, x, *, positions, cache=None, cache_index=None,
                  window=None):
    """Absorbed-form MLA: attention runs as MQA over the compressed latent
    (kv_lora_rank + rope_dim per token) — the cache stores ONLY the latent,
    never expanded K/V.  q_nope is absorbed through W_UK; values are read
    as latent context then expanded through W_UV.  (window unused; MLA
    archs here are full-attention.)"""
    from repro.models import layers as L
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank

    if "w_dqkr" in p:
        # fused down-projections: q-latent, kv-latent, and k-rope all
        # consume x — one pass instead of three x reads
        cq, ckv, kr = L.fused_linear(x, p["w_dqkr"])
    else:
        cq = L.linear(x, p["w_dq"])
        ckv = L.linear(x, p["w_dkv"])                      # [B,S,r]
        kr = L.linear(x, p["w_kr"])
    q = L.linear(cq, p["w_uq"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.rope(q_rope, positions, cfg.rope_theta)

    krope = L.rope(kr[:, :, None, :], positions,
                   cfg.rope_theta)[:, :, 0]                # [B,S,rope_d]

    # absorb: q_abs[b,s,h,r] = q_nope . W_UK(per head)
    w_uk = p["w_uk"].reshape(r, h, nope)
    dt = L.dot_dtype(x.dtype)
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(dt),
                       w_uk.astype(dt),
                       preferred_element_type=jnp.float32).astype(x.dtype)

    new_cache = None
    if cache is not None:
        cc = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_index, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], krope.astype(cache["krope"].dtype), cache_index,
            axis=1)
        new_cache = {"ckv": cc, "krope": cr}
        ckv_all, krope_all = cc, cr
        kv_len = cache_index + s
        q_offset = cache_index
    else:
        ckv_all, krope_all = ckv, krope
        kv_len, q_offset = s, 0

    # MQA over latent: kv head = 1, key dim = r + rope_d, value = latent (r)
    q_full = jnp.concatenate([q_abs, q_rope], axis=-1)      # [B,S,H,r+rd]
    k_full = jnp.concatenate([ckv_all, krope_all],
                             axis=-1)[:, :, None, :]        # [B,T,1,r+rd]
    v_lat = ckv_all[:, :, None, :]                          # [B,T,1,r]
    ctx = blocked_attention(
        q_full, k_full, v_lat, scale=(nope + rope_d) ** -0.5, causal=True,
        kv_len=kv_len, q_offset=q_offset)                   # [B,S,H,r]

    w_uv = p["w_uv"].reshape(r, h, vd)
    out = jnp.einsum("bshr,rhv->bshv", ctx.astype(dt), w_uv.astype(dt),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return L.linear(out.reshape(b, s, h * vd), p["wo"]), new_cache
