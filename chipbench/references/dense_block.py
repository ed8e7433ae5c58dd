"""Plain reference of the dense pre-norm decoder both configurations run,
computed at the precision the configuration states.

Written from the layer equations, importing nothing of the program:

    x_0    = E[tokens]
    h      = rmsnorm(x)                       (scale 1, eps from config)
    q,k,v  = h W_q, h W_k, h W_v              (heads of head_dim)
    q,k    = rope(q), rope(k)                 (full rotary, half-split)
    x      = x + softmax(q k^T / sqrt(head_dim), causal) v  W_o
    h      = rmsnorm(x)
    x      = x + (silu(h W_gate) * (h W_up)) W_down
    logits = rmsnorm(x_L) W_head

Precision, as the configuration states it: weights are held in
``param_dtype`` (float32) and enter every product as they are, at the
backend's default matmul precision (on a TPU, one bfloat16 pass of the
MXU) with float32 accumulation; activations, keys and values are held in
``compute_dtype``/``cache_dtype`` (bfloat16).  Norms, rotary and softmax
run in float32 and round once to the activation dtype; a projection's
epilogue (the gated unit, the residual add of the down projection) runs
on its float32 accumulator and rounds once; the logits round to the
activation dtype.  Softmax runs online over blocks of ``KEY_BLOCK`` keys,
its probabilities rounded to the activation dtype before they weight the
values, as a flash-attention kernel on the MXU computes it.  So a
program that computes what the configuration states differs from this
reference only by the order of float32 sums, and one that computes in a
lower precision (int8 weights) reads well above it.

The weights are regenerated here from the seed with the benchmark's
weight recipe, one layer at a time, so the reference holds one layer's
weights and the sampled sequences' activations and nothing else:

    k_embed, k_layers, k_head = split(key(seed), 4)[:3]
    layer l: k = split(split(k_layers, L)[l], 8)
             q/k/v/o from split(k[0], 4); gate, up, down from k[3..5]
    every matrix [fan_in, fan_out] ~ N(0, 1) / sqrt(fan_in); E ~ N(0, 1)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 256      # sequences are padded to a multiple of this (causal)
KEY_BLOCK = 512   # keys per block of the online softmax
_NEG = -1e30


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(dims, dtype, layer_key):
    d, h, hkv, hd, dff = dims
    k = jax.random.split(layer_key, 8)
    a = jax.random.split(k[0], 4)
    return {"wq": _normal(a[0], (d, h * hd), d ** -0.5, dtype),
            "wk": _normal(a[1], (d, hkv * hd), d ** -0.5, dtype),
            "wv": _normal(a[2], (d, hkv * hd), d ** -0.5, dtype),
            "wo": _normal(a[3], (h * hd, d), (h * hd) ** -0.5, dtype),
            "wg": _normal(k[3], (d, dff), d ** -0.5, dtype),
            "wu": _normal(k[4], (d, dff), d ** -0.5, dtype),
            "wd": _normal(k[5], (dff, d), dff ** -0.5, dtype)}


def _mm(x, w):
    """x @ w with float32 accumulation, at the default precision."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


def _rope(x, theta):
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _attention(q, k, v, scale):
    """Causal softmax(q k^T * scale) v for q [S, H, D], k/v [S, H, D],
    online over key blocks; returns [S, H, D] in q's dtype."""
    s, h, d = q.shape
    pad = -s % KEY_BLOCK
    kb = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(-1, KEY_BLOCK, h, d)
    vb = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(-1, KEY_BLOCK, h, d)
    q_pos = jnp.arange(s)[:, None]

    def block(carry, xs):
        m, l, acc = carry
        k_b, v_b, i = xs
        sc = jnp.einsum("shd,thd->hst", q, k_b,
                        preferred_element_type=jnp.float32) * scale
        mask = (i * KEY_BLOCK + jnp.arange(KEY_BLOCK))[None, :] <= q_pos
        sc = jnp.where(mask, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hst,thd->hsd", p.astype(v.dtype), v_b,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((h, s), _NEG, jnp.float32)
    l0 = jnp.zeros((h, s), jnp.float32)
    a0 = jnp.zeros((h, s, d), jnp.float32)
    (_, l, acc), _ = jax.lax.scan(
        block, (m0, l0, a0), (kb, vb, jnp.arange(kb.shape[0])))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).transpose(1, 0, 2).astype(q.dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(consts, x, w):
    (d, h, hkv, hd, dff), eps, theta, cache_dt = consts
    dt = x.dtype
    s = x.shape[0]
    y = _rms(x, eps)
    q = _mm(y, w["wq"]).astype(dt).reshape(s, h, hd)
    k = _mm(y, w["wk"]).astype(dt).reshape(s, hkv, hd)
    v = _mm(y, w["wv"]).astype(dt).reshape(s, hkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k.astype(cache_dt), h // hkv, axis=1)
    v = jnp.repeat(v.astype(cache_dt), h // hkv, axis=1)
    o = _attention(q.astype(cache_dt), k, v, hd ** -0.5)
    x = x + _mm(o.reshape(s, h * hd).astype(dt), w["wo"]).astype(dt)
    y = _rms(x, eps)
    a = (jax.nn.silu(_mm(y, w["wg"])) * _mm(y, w["wu"])).astype(dt)
    return (_mm(a, w["wd"]) + x.astype(jnp.float32)).astype(dt)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(eps, x, head):
    return _mm(_rms(x, eps), head).astype(x.dtype).astype(jnp.float32)


def _dims(sizes):
    hd = sizes.get("head_dim") or sizes["d_model"] // sizes["num_heads"]
    return (sizes["d_model"], sizes["num_heads"], sizes["num_kv_heads"],
            hd, sizes["d_ff"])


def logits(sizes: dict, seed: int, seqs: list, rows: list, *,
           pad_to: int | None = None) -> list:
    """Logits of each sequence in ``seqs`` (int token arrays) at its
    positions ``rows[i]``, as float32 device arrays ``[len(rows[i]), V]``
    holding values of the activation dtype.  ``sizes`` uses the program's
    field names (num_layers, d_model, ..., param_dtype, compute_dtype,
    cache_dtype).  Every sequence is padded at its end (causal, so the
    padding changes nothing before it) to ``pad_to`` tokens, or to a
    multiple of ``BUCKET``; one length compiles the layer once."""
    dims = _dims(sizes)
    wdt, adt, cdt = (jnp.dtype(sizes[k]) for k in
                     ("param_dtype", "compute_dtype", "cache_dtype"))
    consts = (dims, float(sizes["norm_eps"]), float(sizes["rope_theta"]),
              cdt)
    ks = jax.random.split(jax.random.key(seed), 4)
    embed = _normal(ks[0], (sizes["vocab_size"], sizes["d_model"]), 1.0, wdt)
    xs = []
    for s in seqs:
        pad = (pad_to - len(s)) if pad_to else -len(s) % BUCKET
        toks = jnp.asarray(np.pad(np.asarray(s, np.int32), (0, pad)))
        xs.append(embed[toks].astype(adt))
    del embed
    layer_keys = jax.random.split(ks[1], sizes["num_layers"])
    for li in range(sizes["num_layers"]):
        w = _layer_weights(dims, wdt, layer_keys[li])
        xs = [_layer(consts, x, w) for x in xs]
        del w
    head = _normal(ks[2], (sizes["d_model"], sizes["vocab_size"]),
                   sizes["d_model"] ** -0.5, wdt)
    return [_logits(consts[1], x[jnp.asarray(r)], head)
            for x, r in zip(xs, rows)]
