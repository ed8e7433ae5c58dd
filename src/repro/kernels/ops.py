"""Jit'd convenience wrappers around the Pallas kernels.

``repro.gemm`` (plan/execute) is the deployment surface
(packed/per-call/xla paths); these wrappers expose the raw kernels with
shape massaging for tests and the attention layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import panel_gemm as _pg
from repro.kernels import ref as _ref


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def gemm(x: jax.Array, w: jax.Array, *,
         block_m: int = _pg.DEFAULT_BLOCK_M,
         block_n: int = _pg.DEFAULT_BLOCK_N,
         block_k: int = _pg.DEFAULT_BLOCK_K,
         interpret: bool = False) -> jax.Array:
    """GEMM on arbitrary (M, K) x (K, N): pads to blocks, calls the kernel,
    slices back."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = (min(block_m, _rnd(m)), min(block_n, _rnd(n)),
                  min(block_k, _rnd(k)))
    xp = jnp.pad(x, (((-m) % bm and (0, (-m) % bm)) or (0, 0),
                     ((-k) % bk and (0, (-k) % bk)) or (0, 0)))
    wp = jnp.pad(w, (((-k) % bk and (0, (-k) % bk)) or (0, 0),
                     ((-n) % bn and (0, (-n) % bn)) or (0, 0)))
    y = _pg.panel_gemm(xp, wp, block_m=bm, block_n=bn, block_k=bk,
                       interpret=interpret)
    return y[:m, :n]


def _rnd(x: int, mult: int = 128) -> int:
    """Round up to the MXU lane multiple (small test shapes stay small)."""
    return max(mult, ((x + mult - 1) // mult) * mult)


def mha(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
        block_q: int = _fa.DEFAULT_BLOCK_Q,
        block_kv: int = _fa.DEFAULT_BLOCK_KV,
        interpret: bool = False):
    """Multi-head attention on [B, S, H, D] with GQA kv [B, T, Hkv, D]."""
    b, s, h, d = q.shape
    _, t, hkv, _ = k.shape
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap, scale=scale, block_q=block_q,
                            block_kv=block_kv, interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# Re-export oracles next to the wrappers for test convenience.
ref_gemm = _ref.gemm_xla
ref_gemm_blocked = _ref.gemm_blocked
ref_attention = _ref.attention
