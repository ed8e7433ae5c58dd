"""Mixture-of-Experts: one layer for serving, one for training.

``moe_grouped`` serves (the prefill and decode modes): it routes every
token over all ``num_experts``, keeps the rows routed to the experts this
chip holds (``cfg.held_range``: all of them, or one chip's share under
expert parallelism), sorts them by expert and runs the held experts as
grouped GEMMs over row groups of varying size.  It drops no token, so a
token's output does not depend on its batch.

``moe_ffn`` trains (the train mode, with the load-balance aux loss):
capacity dispatch, tokens past an expert's capacity dropped (static
shapes).  Under a share both leave out what the absent experts add.
Both route by softmax top-k, the top-k weights renormalised when
``cfg.norm_topk_prob`` and scaled by ``cfg.routed_scaling`` (the
deepseek-v3 bias-corrected sigmoid router is simplified to softmax
top-k — recorded in DESIGN.md).
Dispatch avoids the O(T*E*C*d) one-hot einsum:
slot positions come from a cumsum over a (T*k, E) one-hot (int32, no d
factor) and tokens are scatter-added into the (E, C, d) expert buffer —
so compiled FLOPs stay proportional to ACTUAL expert work (capacity * d),
which keeps the roofline MODEL_FLOPS/HLO_FLOPs ratio honest.

Sharding: the expert dim is the 'experts' logical axis (→ model axis, EP);
GSPMD inserts the token all-to-all at the data→expert boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def moe_params(key, cfg, dtype):
    """Router [d, E] (float32), expert banks [held, ...], shared experts.
    Expert e's weights come from ``split(bank key, E)[e]`` and only the
    held experts' are made, so a chip's share holds what the uncut model
    holds for those experts."""
    ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    lo, hi = cfg.held_range

    def bank(k, shape):
        held = jax.random.split(k, e)[lo:hi]
        return jax.vmap(lambda kk: L.init_dense(kk, shape, dtype=dtype))(held)
    p = {"router": L.init_dense(ks[0], (d, e), dtype=jnp.float32),
         "wi_gate": bank(ks[1], (d, f)), "wi_up": bank(ks[2], (d, f)),
         "wo": bank(ks[3], (f, d))}
    if cfg.num_shared_experts:
        sf = cfg.moe_d_ff * cfg.num_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": L.init_dense(sk[0], (d, sf), dtype=dtype),
            "w_up": L.init_dense(sk[1], (d, sf), dtype=dtype),
            "w_down": L.init_dense(sk[2], (sf, d), dtype=dtype),
        }
    return p


def _shared(p, cfg, x2):
    sh_p = p["shared"]
    if "w_gate_up" in sh_p:           # horizontally fused gate+up pack
        return L.swiglu_fused(x2, sh_p["w_gate_up"], sh_p["w_down"], cfg.act)
    return L.swiglu(x2, sh_p["w_gate"], sh_p["w_up"], sh_p["w_down"],
                    cfg.act)


def moe_grouped(p, cfg, x):
    """x: [B, S, d] -> (y [B, S, d], rows [held] int32).

    y = sum over the top-k experts e of each token that this chip holds
    of p_e * SwiGLU_e(x), plus the shared experts' SwiGLU(x); p is the
    router's softmax over all experts, in float32, its top-k weights
    renormalised only when ``cfg.norm_topk_prob`` and scaled by
    ``cfg.routed_scaling``.  rows counts the rows routed to each held
    expert (the step's expert-load counter).

    Device scopes: ``moe_route`` (router, top-k, sorting the held rows
    by expert), ``moe_experts`` (the grouped GEMMs), ``moe_combine``
    (weighting and scatter-adding rows back to their tokens)."""
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    lo, hi = cfg.held_range
    g = hi - lo
    xt = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(
            jnp.float32), precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)                 # [T, k]
        if cfg.norm_topk_prob:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p * cfg.routed_scaling
        e_of = top_i.reshape(-1)
        grp = jnp.where((e_of >= lo) & (e_of < hi), e_of - lo, g)  # g: absent
        order = jnp.argsort(grp, stable=True)                  # held first
        rows = jnp.sum(jax.nn.one_hot(grp, g + 1, dtype=jnp.int32),
                       axis=0)[:g]
        tok = order // k
        xs = xt[tok]                                           # [T*k, d]
        w_row = jnp.where(grp[order] < g, top_p.reshape(-1)[order], 0.0)
    with jax.named_scope("moe_experts"):
        if "wi_gate_up" in p:         # grouped packs (pack_for_inference)
            h = L.grouped_linear(xs, p["wi_gate_up"], rows, glu=cfg.act)
        else:
            h = L.grouped_linear(xs, (p["wi_gate"], p["wi_up"]), rows,
                                 glu=cfg.act)
        y = L.grouped_linear(h, p["wo"], rows, out_dtype=jnp.float32)
    with jax.named_scope("moe_combine"):
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(
            y * w_row[:, None])
    if "shared" in p:
        out = out + _shared(p, cfg, xt).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), rows


def moe_ffn(p, cfg, x, *, capacity_factor: float = 1.25,
            groups: int | None = None, shard_fn=None):
    """x: [B, S, d] -> ([B, S, d], aux_loss).  The training layer:
    tokens past an expert's capacity are dropped (static shapes).

    Dispatch is GROUP-LOCAL (§Perf cell-3): slot assignment (the one-hot
    cumsum) and the scatter into expert buffers run independently per
    token group, with per-group capacity.  With groups = the batch-shard
    count, no dispatch op crosses a batch shard, so GSPMD lowers the
    token→expert movement as an expert-dim all-to-all instead of
    all-gathering the full fp32 token tensor to every device (measured
    3.2 TB/device/step on qwen3 train_4k × multi-pod).  groups=1
    reproduces the global-cumsum baseline.  Per-group capacity is how
    production MoE systems bound dispatch anyway (local capacity ≈
    global/G; imbalance beyond it drops — recorded, not hidden).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    lo, hi = cfg.held_range
    eh = hi - lo                      # held experts; the rest are absent
    t = b * s
    g = groups or getattr(cfg, "moe_dispatch_groups", 0) or 1
    if t % g:
        g = 1
    # capacity-floor guard: per-group capacity can't drop below ~8 slots,
    # so grouping tiny token counts (decode) would inflate total buffer
    # slots ×G (measured 3× on deepseek-v3 decode) — fall back to global.
    if g > 1 and (t // g) * k < 4 * e:
        g = 1
    tg = t // g
    # dispatch/combine constraints only under grouped dispatch; the
    # global path keeps pure propagation (its measured optimum on dsv3)
    shard = (shard_fn if (shard_fn and g > 1) else (lambda a, *n: a))
    xt = shard(x.reshape(g, tg, d), "expert_group", None, None)
    capacity = max(int(tg * k * capacity_factor / e), 4)
    # round capacity to an 8-multiple (TPU sublane) without exceeding tg
    capacity = min(((capacity + 7) // 8) * 8, tg)

    def dispatch_one(xt_g):
        """One group: route, scatter, expert-FFN, combine."""
        logits = L.linear(xt_g.astype(jnp.float32), p["router"])  # [Tg,E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)                 # [Tg, k]
        if cfg.norm_topk_prob:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p * cfg.routed_scaling

        flat_e = top_i.reshape(-1)                             # [Tg*k]
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # [Tg*k, E]
        pos = jnp.cumsum(oh, axis=0) - 1                       # slot ids
        slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = (slot < capacity) & (flat_e >= lo) & (flat_e < hi)
        e_idx = jnp.where(keep, flat_e - lo, eh)  # OOB -> dropped
        s_idx = jnp.where(keep, slot, capacity)

        xk = jnp.repeat(xt_g, k, axis=0)                       # [Tg*k, d]
        buf = jnp.zeros((eh + 1, capacity + 1, d), x.dtype)
        buf = buf.at[e_idx, s_idx].add(xk)
        return (buf[:eh, :capacity], e_idx, s_idx, top_p, probs, oh)

    buf, e_idx, s_idx, top_p, probs, oh = jax.vmap(dispatch_one)(xt)
    # buf: [G, E, C, d] — the dispatch writes it group-local (G on the
    # batch axes); the constraint below re-shards E onto `model`, which
    # GSPMD lowers as the expert all-to-all (the GShard pattern), instead
    # of all-gathering tokens to every device.
    buf = shard(buf, "expert_group", "experts", None, None)

    dt = L.dot_dtype(x.dtype)
    hg = jnp.einsum("gecd,edf->gecf", buf.astype(dt),
                    p["wi_gate"].astype(dt),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    hu = jnp.einsum("gecd,edf->gecf", buf.astype(dt),
                    p["wi_up"].astype(dt),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    ho = jnp.einsum("gecf,efd->gecd",
                    (jax.nn.silu(hg) * hu).astype(dt),
                    p["wo"].astype(dt),
                    preferred_element_type=jnp.float32).astype(x.dtype)

    # combine: bring expert outputs back to their token's group shard
    # (the return all-to-all), then gather per-token rows locally
    ho = shard(ho, "expert_group", None, None, None)
    ho = jnp.pad(ho, ((0, 0), (0, 1), (0, 1), (0, 0)))     # OOB row = 0
    out_tok = jax.vmap(lambda h, ei, si: h[ei, si])(ho, e_idx, s_idx)
    out = jnp.sum(out_tok.reshape(g, tg, k, d)
                  * top_p.reshape(g, tg, k, 1).astype(x.dtype), axis=2)
    out = out.reshape(t, d)

    if "shared" in p:
        out = out + _shared(p, cfg, xt.reshape(t, d))

    # Switch-style load-balance aux loss, from the probs already computed.
    frac_tokens = jnp.mean(oh.astype(jnp.float32).reshape(t, k, e),
                           axis=(0, 1)) * k
    frac_probs = jnp.mean(probs.reshape(t, e), axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out.reshape(b, s, d), aux
