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
    """Multi-head Latent Attention weights: W_q (or DeepSeek-V3's q-LoRA
    pair w_dq/w_uq), the latent's down-projection w_dkv and rope key
    w_kr (together the release's W_kv_a), the latent's up-projections
    w_uk/w_uv (together W_kv_b, per head), W_o, and the latent's RMSNorm
    scale when ``cfg.kv_latent_norm``."""
    from repro.models import layers as L
    ks = jax.random.split(key, 8)
    d, h = cfg.d_model, cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        p = {"w_dq": L.init_dense(ks[0], (d, cfg.q_lora_rank), dtype=dtype),
             "w_uq": L.init_dense(ks[1], (cfg.q_lora_rank,
                                          h * (nope + rope_d)), dtype=dtype)}
    else:
        p = {"wq": L.init_dense(ks[0], (d, h * (nope + rope_d)),
                                dtype=dtype)}
    p.update({
        "w_dkv": L.init_dense(ks[2], (d, cfg.kv_lora_rank), dtype=dtype),
        "w_kr": L.init_dense(ks[3], (d, rope_d), dtype=dtype),
        "w_uk": L.init_dense(ks[4], (cfg.kv_lora_rank, h * nope),
                             dtype=dtype),
        "w_uv": L.init_dense(ks[5], (cfg.kv_lora_rank, h * vd), dtype=dtype),
        "wo": L.init_dense(ks[6], (h * vd, d), dtype=dtype),
    })
    if cfg.kv_latent_norm:
        p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dtype)
    return p


def mla_scale(cfg) -> float:
    """Softmax scale over the (nope + rope)-wide query/key dot; YaRN
    multiplies it by mscale(factor, mscale_all_dim)^2."""
    from repro.models import layers as L
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_project(p, cfg, x, positions):
    """The block's projections of x: (q_nope [B,S,H,nope], q_pe
    [B,S,H,rope] roped, the latent c_kv [B,S,r] (normed when the config
    says so), k_pe [B,S,rope] roped)."""
    from repro.models import layers as L
    b, s, _ = x.shape
    h, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if "w_dqkr" in p:
        # fused down-projections: q-latent, kv-latent, and k-rope all
        # consume x — one pass instead of three x reads
        cq, ckv, kr = L.fused_linear(x, p["w_dqkr"])
        q = L.linear(cq, p["w_uq"])
    elif "w_qkr" in p:
        # no q-LoRA: W_q and W_kv_a both read x, one fused pass
        q, ckv, kr = L.fused_linear(x, p["w_qkr"])
    else:
        ckv = L.linear(x, p["w_dkv"])                      # [B,S,r]
        kr = L.linear(x, p["w_kr"])
        q = (L.linear(x, p["wq"]) if "wq" in p
             else L.linear(L.linear(x, p["w_dq"]), p["w_uq"]))
    q = q.reshape(b, s, h, nope + rope_d)
    if "kv_norm" in p:
        ckv = L.rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    freq = L.rope_freqs(cfg, rope_d)
    q_pe = L.rope(q[..., nope:], positions, cfg.rope_theta, freq)
    kpe = L.rope(kr[:, :, None, :], positions, cfg.rope_theta,
                 freq)[:, :, 0]                            # [B,S,rope_d]
    return q[..., :nope], q_pe, ckv, kpe


def mla_attention(p, cfg, x, *, positions, cache=None, cache_index=None,
                  window=None, page_size=None, mode="prefill"):
    """Multi-head Latent Attention.  The cache stores only the latent
    (kv_lora_rank + rope_dim per token), never expanded K/V.

    A dense ``ckv``/``krope`` cache (or none) runs the absorbed form: MQA
    over the latent, q_nope absorbed through W_UK, the latent context
    expanded through W_UV.  A paged cache (``pages_lat``, the stacked
    latent pool of runtime/kv_cache, with the scan's layer index) is
    written with this step's latents and read block by block up to the
    live length (``_mla_paged``).  (window unused; MLA archs here are
    full-attention.)"""
    from repro.models import layers as L
    b, s, _ = x.shape
    h = cfg.num_heads
    vd = cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_pe, ckv, krope = _mla_project(p, cfg, x, positions)

    if cache is not None and "pages_lat" in cache:
        out, new_cache = _mla_paged(p, cfg, q_nope, q_pe, ckv, krope, cache,
                                    page_size=page_size,
                                    absorbed=(mode == "decode"
                                              or MLA_PREFILL_ABSORBED))
        return L.linear(out.reshape(b, s, h * vd), p["wo"]), new_cache

    # absorb: q_abs[b,s,h,r] = q_nope . W_UK(per head)
    nope = cfg.qk_nope_dim
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
    q_full = jnp.concatenate([q_abs, q_pe], axis=-1)        # [B,S,H,r+rd]
    k_full = jnp.concatenate([ckv_all, krope_all],
                             axis=-1)[:, :, None, :]        # [B,T,1,r+rd]
    v_lat = ckv_all[:, :, None, :]                          # [B,T,1,r]
    ctx = blocked_attention(
        q_full, k_full, v_lat, scale=mla_scale(cfg), causal=True,
        kv_len=kv_len, q_offset=q_offset)                   # [B,S,H,r]

    w_uv = p["w_uv"].reshape(r, h, vd)
    out = jnp.einsum("bshr,rhv->bshv", ctx.astype(dt), w_uv.astype(dt),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return L.linear(out.reshape(b, s, h * vd), p["wo"]), new_cache


MLA_KEY_PAGES = 32       # pages per key block of the paged MLA loop
# The paged prefill step expands its key blocks through W_kv_b: on a v5e
# at 1024-row chunks that beat the absorbed form at every context
# (docs/serving.md).  True runs prefill absorbed, as decode runs.
MLA_PREFILL_ABSORBED = False


def _mla_paged(p, cfg, q_nope, q_pe, ckv, kpe, cache, *, page_size,
               absorbed: bool):
    """MLA over the paged latent pool.  Writes this step's rows
    ``[c_kv | k_pe]`` at each slot's length (``kv_write``), then attends
    over the slot's pages in blocks of ``MLA_KEY_PAGES`` pages, only as
    many blocks as the longest live slot needs (a traced trip count), with
    an online softmax.  Each block is gathered from the pool in place
    (``kv_view``) and:

    * absorbed (decode; prefill when ``MLA_PREFILL_ABSORBED``): scored
      as MQA over the 576-wide latent, q_nope absorbed through W_UK
      (``mla_absorb``), the latent context expanded through W_UV once at
      the end;
    * expanded (prefill): expanded through W_kv_b into per-head keys and
      values (``mla_expand``), then ordinary attention over 192-wide keys.

    Returns (out [B, S, H, v_head_dim], the pool)."""
    from repro.models import layers as L
    from repro.runtime import kv_cache as KV
    pt, lens, li = cache["page_table"], cache["lens"], cache["layer"]
    b, s, h, nope = q_nope.shape
    r, vd, rope_d = cfg.kv_lora_rank, cfg.v_head_dim, cfg.qk_rope_dim
    cdt = cache["pages_lat"].dtype
    dt = L.dot_dtype(cdt)
    # the pool's rows are r + rope_d padded to whole lanes (leaf_specs_for)
    lat = jnp.concatenate([ckv, kpe], axis=-1)               # [B,S,r+rd]
    width = cache["pages_lat"].shape[-1]
    lat = jnp.pad(lat, ((0, 0), (0, 0), (0, width - lat.shape[-1])))
    pool = KV.paged_update(cache["pages_lat"], lat, pt, lens, page_size,
                           li, write_mask=cache.get("write_mask"))
    w_uk = p["w_uk"].reshape(r, h, nope).astype(dt)
    w_uv = p["w_uv"].reshape(r, h, vd).astype(dt)
    q_pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]   # [B,S]
    kv_len = lens + s

    if absorbed:
        with jax.named_scope("mla_absorb"):
            q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(dt), w_uk,
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_abs.astype(cdt), q_pe.astype(cdt)], -1)

        def kv_of(blk):                                      # MQA: 1 head
            return blk[:, :, None, :r + rope_d], blk[:, :, None, :r]
    else:
        q = jnp.concatenate([q_nope, q_pe], axis=-1).astype(cdt)

        def kv_of(blk):
            with jax.named_scope("mla_expand"):
                c = blk[..., :r].astype(dt)
                k_nope = jnp.einsum("btr,rhn->bthn", c, w_uk,
                                    preferred_element_type=jnp.float32)
                v = jnp.einsum("btr,rhv->bthv", c, w_uv,
                               preferred_element_type=jnp.float32)
                k_pe = jnp.broadcast_to(blk[:, :, None, r:r + rope_d],
                                        k_nope.shape[:3] + (rope_d,))
                k = jnp.concatenate([k_nope.astype(cdt), k_pe], -1)
                return k, v.astype(cdt)

    out = _paged_blocks(q, pool, pt, page_size, li, kv_len, q_pos, kv_of,
                        scale=mla_scale(cfg))
    if absorbed:
        with jax.named_scope("mla_absorb"):
            out = jnp.einsum("bshr,rhv->bshv", out.astype(dt), w_uv,
                             preferred_element_type=jnp.float32)
    return out.astype(q_nope.dtype), {"pages_lat": pool}


def _paged_blocks(q, pool, page_table, page_size, layer, kv_len, q_pos,
                  kv_of, *, scale):
    """Causal online-softmax attention of q [B,S,H,D] over each slot's
    paged rows, ``MLA_KEY_PAGES`` pages a block, for
    ceil(max(kv_len) / block) blocks.  ``kv_of(rows [B,T,F])`` returns
    the block's keys [B,T,Hk,D] and values [B,T,Hk,Dv] (Hk divides H).
    Probabilities round to the values' dtype before they weight them, as
    ``blocked_attention`` does.  Returns [B,S,H,Dv] float32."""
    from repro.models import layers as L
    from repro.runtime import kv_cache as KV
    b, s, h, d = q.shape
    tb = MLA_KEY_PAGES * page_size
    n_pages = page_table.shape[1]
    with jax.named_scope("kv_view"):
        pt = jnp.pad(page_table, ((0, 0), (0, (-n_pages) % MLA_KEY_PAGES)),
                     constant_values=-1)
    n_blocks = (jnp.max(kv_len) + tb - 1) // tb
    k0, v0 = jax.eval_shape(
        kv_of, jax.ShapeDtypeStruct((b, tb, pool.shape[-1]), pool.dtype))
    hk, dv = k0.shape[2], v0.shape[3]
    g = h // hk
    dt = L.dot_dtype(q.dtype)
    qg = q.reshape(b, s, hk, g, d).astype(dt)

    def body(j, carry):
        m, l, acc = carry
        with jax.named_scope("kv_view"):
            pt_j = jax.lax.dynamic_slice_in_dim(pt, j * MLA_KEY_PAGES,
                                                MLA_KEY_PAGES, axis=1)
            rows = KV.paged_gather(pool, pt_j, page_size, layer)
        k, v = kv_of(rows)
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(dt),
                        preferred_element_type=jnp.float32) * scale
        k_pos = j * tb + jnp.arange(tb, dtype=jnp.int32)
        mask = ((k_pos[None, None, :] < kv_len[:, None, None])
                & (k_pos[None, None, :] <= q_pos[:, :, None]))   # [B,S,T]
        mask = mask[:, None, None]
        sc = jnp.where(mask, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        pr = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pr, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkv->bkgsv", pr.astype(v.dtype).astype(dt),
            v.astype(dt), preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((b, hk, g, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hk, g, s), jnp.float32)
    a0 = jnp.zeros((b, hk, g, s, dv), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)      # [B,S,Hk,G,Dv]
    return out.reshape(b, s, h, dv)
