"""Plain reference of the DeepSeek-V2 block (latent attention, routed and
shared experts, a leading dense layer), at the precision the
configuration states, for one chip's share of the routed experts.

Written from the release's equations, importing nothing of the program.
Per layer l, with h = rmsnorm(x) (scale 1, eps from the config):

    q            = h W_q                       -> H heads of [nope | rope]
    [c | k_pe]   = h W_kv_a,  c = rmsnorm(c)   (the latent, r wide)
    k_nope, v    = c W_uk, c W_uv              (W_kv_b, per head)
    q_pe, k_pe   = yarn_rope(q_pe), yarn_rope(k_pe)   (k_pe shared by heads)
    x            = x + softmax((q_nope k_nope + q_pe k_pe) * scale,
                               causal) v  W_o
    scale        = (nope + rope)^-0.5 * mscale^2,
    mscale       = 0.1 * mscale_all_dim * ln(factor) + 1
    h            = rmsnorm(x)
    l < first_k_dense_replace:
        x = x + (silu(h W_gate) * (h W_up)) W_down
    else:
        p = softmax(h W_router)   (float32, over all n_routed_experts
                                   of the router, router_experts wide)
        keep the top num_experts_per_tok weights, unnormalised
        (norm_topk_prob false) times routed_scaling_factor
        y = sum over the held experts e among them of
            p_e (silu(h W_g,e) * (h W_u,e)) W_d,e
            + (silu(h W_sg) * (h W_su)) W_sd   (the shared experts)
        x = x + y
    logits = rmsnorm(x_L) W_head

YaRN frequencies (arXiv:2309.00071, as DeepSeek-V2 computes them): pair i
of the rope width d rotates at theta^(-2i/d) blended toward that over
``factor`` by a ramp from pair ``low`` to ``high``, where
low/high = floor/ceil of d ln(original_max / (beta * 2 pi)) / (2 ln theta)
at beta_fast/beta_slow; cos and sin are unscaled (mscale equals
mscale_all_dim).  Rope rotates half-split pairs (i, i + d/2).

Held experts: the configuration holds ``n_routed_experts`` of the
router's ``router_experts`` (experts 0 .. n-1); what the others would add
is left out here as in the program.

Precision, as the configuration states it: weights in ``param_dtype``
(float32) enter every product as they are, at the backend's default
matmul precision with float32 accumulation, except the router, which
multiplies at full float32 precision as the release's gate does;
activations, the latent cache and the expanded keys and values are held
in the activation/cache dtype (bfloat16).  Norms, rope, the router's
softmax and attention's softmax run in float32 and round once; the gated
unit runs on its float32 accumulators and rounds once; the routed
experts' weighted sum accumulates in float32 with the shared experts'
output (rounded, as a projection's output is) and rounds once.
Attention runs online over blocks of ``KEY_BLOCK`` keys for blocks of
``QUERY_BLOCK`` queries, probabilities rounded before they weight the
values, so a 16k-token sequence fits.

The weights are regenerated from the seed with the program's
``init_params`` recipe, one layer at a time:

    k_embed, k_layers, k_head = split(key(seed), 4)[:3]
    E ~ N(0, 1); every matrix [fan_in, fan_out] ~ N(0, 1) / sqrt(fan_in)
    layer l: k = split(split(k_layers, L)[l], 8)
      attention a = split(k[0], 8): W_q a[0], W_dkv a[2], W_kr a[3]
        (W_kv_a = [W_dkv | W_kr]), W_uk a[4], W_uv a[5], W_o a[6]
      dense layer: W_gate k[3], W_up k[4], W_down k[5]
      MoE layer m = split(k[2], 5): W_router m[0] (float32);
        expert e: W_g split(m[1], E)[e], W_u split(m[2], E)[e],
        W_d split(m[3], E)[e]; shared s = split(m[4], 3): W_sg s[0],
        W_su s[1], W_sd s[2] (n_shared_experts * moe_intermediate_size)
"""
from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = "deepseek-v2-lite-14l"   # chipbench/configs/<CONFIG>.json
BUCKET = 256       # sequences are padded to a multiple of this (causal)
KEY_BLOCK = 512    # keys per block of the online softmax
QUERY_BLOCK = 2048
_NEG = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _mm(x, w):
    """x @ w with float32 accumulation, at the default precision."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


def _swiglu(h, wg, wu):
    return jax.nn.silu(_mm(h, wg)) * _mm(h, wu)


def yarn_freqs(d, theta, factor, original_max, beta_fast, beta_slow):
    """[d/2] rotation frequencies of YaRN rope (module docstring)."""
    half = d // 2
    extra = theta ** (-np.arange(0, half, dtype=np.float64) / half)

    def dim_at(turns):
        return (d * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), d - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(extra / factor * ramp + extra * (1 - ramp),
                       jnp.float32), (low, high)


def _rope(x, freq):
    """x [S, H, d]: half-split rotary at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, scale):
    """Causal softmax(q k^T * scale) v for q, k [S, H, D], v [S, H, Dv],
    online over key blocks, query block by query block."""
    s, h, d = q.shape
    dv = v.shape[-1]
    kb = k.reshape(-1, KEY_BLOCK, h, d)
    vb = v.reshape(-1, KEY_BLOCK, h, dv)
    qblk = min(QUERY_BLOCK, s)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qblk, qblk, 0)
        q_pos = i * qblk + jnp.arange(qblk)[:, None]

        def block(carry, xs):
            m, l, acc = carry
            k_b, v_b, j = xs
            sc = jnp.einsum("shd,thd->hst", qi, k_b,
                            preferred_element_type=jnp.float32) * scale
            mask = (j * KEY_BLOCK + jnp.arange(KEY_BLOCK))[None, :] <= q_pos
            sc = jnp.where(mask, sc, _NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "hst,thd->hsd", p.astype(v.dtype), v_b,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((h, qblk), _NEG, jnp.float32)
        l0 = jnp.zeros((h, qblk), jnp.float32)
        a0 = jnp.zeros((h, qblk, dv), jnp.float32)
        (_, l, acc), _ = jax.lax.scan(
            block, (m0, l0, a0), (kb, vb, jnp.arange(kb.shape[0])))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).transpose(1, 0, 2).astype(q.dtype)

    return jax.lax.map(one, jnp.arange(s // qblk)).reshape(s, h, dv)


def _dims(c):
    return (c["d"], c["h"], c["nope"], c["rope"], c["vd"], c["r"])


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_weights(c, dense, dtype, layer_key):
    c = dict(c)
    d, h, nope, rope, vd, r = _dims(c)
    k = jax.random.split(layer_key, 8)
    a = jax.random.split(k[0], 8)
    w = {"wq": _normal(a[0], (d, h * (nope + rope)), d ** -0.5, dtype),
         "w_kv_a": jnp.concatenate(
             [_normal(a[2], (d, r), d ** -0.5, dtype),
              _normal(a[3], (d, rope), d ** -0.5, dtype)], axis=1),
         "w_uk": _normal(a[4], (r, h * nope), r ** -0.5, dtype),
         "w_uv": _normal(a[5], (r, h * vd), r ** -0.5, dtype),
         "wo": _normal(a[6], (h * vd, d), (h * vd) ** -0.5, dtype)}
    if dense:
        dff = c["dff"]
        w.update(wg=_normal(k[3], (d, dff), d ** -0.5, dtype),
                 wu=_normal(k[4], (d, dff), d ** -0.5, dtype),
                 wd=_normal(k[5], (dff, d), dff ** -0.5, dtype))
        return w
    m = jax.random.split(k[2], 5)
    e_all, held, f = c["router_experts"], c["held"], c["f"]

    def bank(key, shape):
        keys = jax.random.split(key, e_all)[:held]
        return jax.vmap(lambda kk: _normal(kk, shape, shape[0] ** -0.5,
                                           dtype))(keys)
    sf = f * c["n_shared"]
    s = jax.random.split(m[4], 3)
    w.update(router=_normal(m[0], (d, e_all), d ** -0.5, jnp.float32),
             eg=bank(m[1], (d, f)), eu=bank(m[2], (d, f)),
             ed=bank(m[3], (f, d)),
             sg=_normal(s[0], (d, sf), d ** -0.5, dtype),
             su=_normal(s[1], (d, sf), d ** -0.5, dtype),
             sd=_normal(s[2], (sf, d), sf ** -0.5, dtype))
    return w


def _attn(c, x, w, freq, cdt):
    d, h, nope, rope, vd, r = _dims(c)
    dt = x.dtype
    s = x.shape[0]
    y = _rms(x, c["eps"])
    q = _mm(y, w["wq"]).astype(dt).reshape(s, h, nope + rope)
    kv_a = _mm(y, w["w_kv_a"]).astype(dt)
    lat = _rms(kv_a[:, :r], c["eps"]).astype(cdt)
    k_pe = _rope(kv_a[:, None, r:], freq).astype(cdt)            # [S,1,rope]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq)], -1)
    k_nope = _mm(lat, w["w_uk"]).astype(cdt).reshape(s, h, nope)
    v = _mm(lat, w["w_uv"]).astype(cdt).reshape(s, h, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, h, rope))], -1)
    o = _attention(q.astype(cdt), k, v, c["scale"])
    return x + _mm(o.reshape(s, h * vd).astype(dt), w["wo"]).astype(dt)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _dense_layer(c, x, w, freq, cdt):
    c = dict(c)
    x = _attn(c, x, w, freq, cdt)
    y = _rms(x, c["eps"])
    a = _swiglu(y, w["wg"], w["wu"]).astype(x.dtype)
    return (_mm(a, w["wd"]) + x.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _moe_layer(c, x, w, freq, cdt):
    c = dict(c)
    x = _attn(c, x, w, freq, cdt)
    dt = x.dtype
    y = _rms(x, c["eps"])
    p = jax.nn.softmax(jnp.dot(y.astype(jnp.float32), w["router"],
                               precision=_HIGHEST), axis=-1)
    top_p, top_i = jax.lax.top_k(p, c["top_k"])
    if c["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * c["routed_scaling"]

    def expert(acc, e):
        wt = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)   # [S]
        a = _swiglu(y, w["eg"][e], w["eu"][e]).astype(dt)
        return acc + _mm(a, w["ed"][e]) * wt[:, None], None

    routed, _ = jax.lax.scan(expert, jnp.zeros(x.shape, jnp.float32),
                             jnp.arange(c["held"]))
    shared = _mm(_swiglu(y, w["sg"], w["su"]).astype(dt), w["sd"]).astype(dt)
    return x + (routed + shared.astype(jnp.float32)).astype(dt)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(eps, x, head):
    return _mm(_rms(x, eps), head).astype(x.dtype).astype(jnp.float32)


def block_consts(config: dict, sizes: dict) -> dict:
    """The block's sizes from a configuration file's published keys
    (``config``) and the harness's ``sizes`` (program field names)."""
    rs = config["rope_scaling"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    mscale = (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
              if rs["factor"] > 1 else 1.0)
    return {"d": sizes["d_model"], "h": sizes["num_heads"], "nope": nope,
            "rope": rope, "vd": config["v_head_dim"],
            "r": config["kv_lora_rank"], "dff": sizes["d_ff"],
            "f": config["moe_intermediate_size"],
            "n_shared": config["n_shared_experts"],
            "router_experts": config["router_experts"],
            "held": config["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "norm_topk": bool(config["norm_topk_prob"]),
            "routed_scaling": float(config["routed_scaling_factor"]),
            "first_dense": config["first_k_dense_replace"],
            "eps": float(sizes["norm_eps"]),
            "scale": (nope + rope) ** -0.5 * mscale ** 2,
            "yarn": (float(sizes["rope_theta"]), float(rs["factor"]),
                     int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"]))}


def logits_for(config: dict, sizes: dict, seed: int, seqs: list, rows: list,
               *, pad_to: int | None = None) -> list:
    """``logits`` for the block ``config`` (a configuration file's keys)
    describes; ``sizes`` uses the program's field names."""
    c = block_consts(config, sizes)
    wdt, adt, cdt = (jnp.dtype(sizes[k]) for k in
                     ("param_dtype", "compute_dtype", "cache_dtype"))
    theta, factor, omax, bf, bs = c["yarn"]
    freq, _ = yarn_freqs(c["rope"], theta, factor, omax, bf, bs)
    cc = tuple(sorted(c.items()))          # hashable: a static argument
    ks = jax.random.split(jax.random.key(seed), 4)
    embed = _normal(ks[0], (sizes["vocab_size"], c["d"]), 1.0, wdt)
    xs = []
    for s in seqs:
        n = pad_to or len(s) + (-len(s) % BUCKET)
        n += -n % (QUERY_BLOCK if n > QUERY_BLOCK else KEY_BLOCK)
        toks = jnp.asarray(np.pad(np.asarray(s, np.int32), (0, n - len(s))))
        xs.append(embed[toks].astype(adt))
    del embed
    layer_keys = jax.random.split(ks[1], sizes["num_layers"])
    for li in range(sizes["num_layers"]):
        dense = li < c["first_dense"]
        w = _layer_weights(cc, dense, wdt, layer_keys[li])
        fn = _dense_layer if dense else _moe_layer
        xs = [fn(cc, x, w, freq, cdt) for x in xs]
        del w
    head = _normal(ks[2], (c["d"], sizes["vocab_size"]), c["d"] ** -0.5, wdt)
    return [_logits(c["eps"], x[jnp.asarray(r)], head)
            for x, r in zip(xs, rows)]


def logits(sizes: dict, seed: int, seqs: list, rows: list, *,
           pad_to: int | None = None) -> list:
    """Logits of each sequence in ``seqs`` (int token arrays) at its
    positions ``rows[i]``, as float32 device arrays ``[len(rows[i]), V]``
    holding values of the activation dtype.  The harness hands only the
    keys its field map knows (``sizes``); the latent attention, expert and
    YaRN keys come from ``chipbench/configs/<CONFIG>.json``.  Every
    sequence is padded at its end (causal, so the padding changes nothing
    before it) to ``pad_to`` tokens, or to a multiple of ``BUCKET``,
    then to whole query blocks."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", CONFIG + ".json")
    with open(path) as f:
        config = json.load(f)
    return logits_for(config, sizes, seed, seqs, rows, pad_to=pad_to)
