"""Chunked SSD (Mamba-2 state-space duality) as a Pallas TPU kernel.

The jnp implementation (models/ssm.ssd_chunked) materializes the
intra-chunk quadratic blocks (the [l, l] decay matrix and C·Bᵀ scores)
in HBM — the dominant memory term on SSM/hybrid train cells (§Roofline).
This kernel keeps them in VMEM, exactly like the flash-attention kernel
keeps its score blocks resident: HBM sees only x/a/b/c reads and y
writes, one pass.

Layout: grid = (B·H, T/l) with the chunk axis innermost ("arbitrary");
the inter-chunk state [P, N] lives in a VMEM scratch carried across
chunk steps (zeroed at chunk 0 — the panel-GEMM Z-discipline again).
Group-shared B/C are read through the BlockSpec index_map (no
materialized repeat to H heads).

Per (bh, c) step, VMEM working set ≈ l·P + 2·l·N + l·l + P·N floats —
l=128, P=64, N=128 ⇒ ~0.3 MB, comfortably under budget
(vmem_bytes() below).

Oracle: models/ssm.ssd_chunked (pure jnp); parity asserted in
tests/test_ssd_kernel.py across shapes/dtypes in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 128


def vmem_bytes(l: int, p: int, n: int) -> int:
    work = l * p + 2 * l * n + l * l + p * n + l * p
    return 2 * work * 4


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x_c = x_ref[0].astype(jnp.float32)          # [l, P]
    a_c = a_ref[0].astype(jnp.float32)          # [l]
    b_c = b_ref[0].astype(jnp.float32)          # [l, N]
    c_c = c_ref[0].astype(jnp.float32)          # [l, N]
    l = a_c.shape[0]

    a_cum = jnp.cumsum(a_c)                     # [l]
    # intra-chunk decay: L[i,j] = exp(sum_{j<k<=i} a_k), lower-triangular
    seg = a_cum[:, None] - a_cum[None, :]       # [l, l]
    tril = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    lmat = jnp.where(tril, jnp.exp(seg), 0.0)

    cb = jnp.dot(c_c, b_c.T, preferred_element_type=jnp.float32)
    y_diag = jnp.dot(cb * lmat, x_c, preferred_element_type=jnp.float32)

    state = state_ref[...]                      # [P, N]
    y_off = jnp.dot(c_c, state.T,
                    preferred_element_type=jnp.float32) \
        * jnp.exp(a_cum)[:, None]               # [l, P]... see note

    # state update: decay the carry, add this chunk's contribution
    decay = jnp.exp(a_cum[-1] - a_cum)          # [l]
    chunk_state = jnp.dot(x_c.T, b_c * decay[:, None],
                          preferred_element_type=jnp.float32)  # [P, N]
    state_ref[...] = state * jnp.exp(a_cum[-1]) + chunk_state

    o_ref[0] = (y_diag + y_off).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, a, b, c, *, chunk: int = DEFAULT_CHUNK,
        interpret: bool = False):
    """y = chunked-SSD(x, a, b, c), heads flattened.

    x: [BH, T, P] (dt-premultiplied); a: [BH, T] (= A·dt, ≤ 0);
    b, c: [BH, T, N] (groups pre-broadcast by the caller's index_map or
    repeat).  T must be a chunk multiple.  Returns y: [BH, T, P].
    Final state is recomputed by the caller when needed (serving uses
    the jnp path; this kernel is the training/prefill hot loop).
    """
    bh, t, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    return pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        name="ssd",
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk), lambda i, ci: (i, ci)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, a, b, c)
