"""Blocked (flash) causal attention for TPU — the prefill hot-spot.

Same structural discipline as panel_gemm: the online-softmax state
(m, l, acc) is VMEM scratch carried across the innermost ("arbitrary")
KV grid dimension, zero/neg-inf-initialised at kv_block == 0 (skip-Z
analogue) and stored once at the last KV block (STZ analogue).

Supports: causal masking, sliding window, tanh logit softcap (gemma2),
GQA via pre-repeated KV heads (wrapper in ops.py maps kv->q heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128

_NEG_INF = -1e30  # finite stand-in; avoids exp(-inf - -inf) NaNs


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  nkv: int, block_q: int, block_kv: int, kv_len: int,
                  q_offset: int, causal: bool, window, softcap, scale):
    jq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = jq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = jk * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    # Block-level skip: entirely-masked (q, kv) tiles do no work.
    run = True
    if causal:
        run = (jk * block_kv) <= (jq * block_q + q_offset + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bkv, d]
        v = v_ref[0].astype(jnp.float32)            # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                          # [bq, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])        # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])                        # [bq, bkv]
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jk == nkv - 1)
    def _store():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale",
                     "block_q", "block_kv", "interpret"))
def flash_attention(
    q: jax.Array,      # [BH, Sq, D]   (batch*heads flattened, kv pre-repeated)
    k: jax.Array,      # [BH, Skv, D]
    v: jax.Array,      # [BH, Skv, D]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    scale = scale if scale is not None else float(d) ** -0.5

    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    nkv = skv_p // block_kv

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, nkv=nkv, block_q=block_q, block_kv=block_kv,
            kv_len=skv, q_offset=skv - sq, causal=causal, window=window,
            softcap=softcap, scale=scale),
        name="flash_attention",
        grid=(bh, sq_p // block_q, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
