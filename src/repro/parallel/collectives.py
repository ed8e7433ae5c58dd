"""Overlapped collective matmuls — paper lever 1 at mesh scale.

The paper's Fig. 2 lesson: a coarse column panel serializes the machine
(one AMX block idle); panels fine enough to give every compute unit work
recover the 2-block aggregate.  The distributed analogue: a GEMM whose
operand needs an all-gather can either (a) all-gather THEN matmul — the
collective and the MXU serialize, the mesh-scale "coarse panel" — or
(b) decompose the GEMM into one panel per shard and rotate shards around
the ring with `ppermute`, so step i's compute hides step i+1's transfer
(the "collective matmul" of Wang et al. 2023, which XLA's
latency-hiding-scheduler also derives when the panels exist for it to
schedule).  These shard_map implementations make the decomposition
explicit and testable; the dry-run's HLO shows `collective-permute` ops
interleaved with per-panel dots instead of one monolithic all-gather.

All three are bit-stable per panel: each output tile is produced by
exactly one dot (ag_matmul) or a fixed-order chain of adds (matmul_rs),
matching the kernel's blocked-oracle discipline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring(axis: str, size: int, fwd: bool = True):
    if fwd:
        return [(i, (i + 1) % size) for i in range(size)]
    return [((i + 1) % size, i) for i in range(size)]


def ag_matmul(x, w, *, mesh: Mesh, axis: str = "model"):
    """y = all_gather(x, K-axis) @ w, overlapped.

    x: [M, K/s] sharded over `axis` on K; w: [K, N/s] sharded over `axis`
    on N (column-parallel layer).  Each device computes its N-panel of the
    full y by accumulating K-panels as they arrive around the ring:
    y_local[M, N/s] = Σ_i x_i @ w[K_i, local].  Compute of panel i overlaps
    the ppermute bringing panel i+1.
    """
    s = mesh.shape[axis]
    perm = _ring(axis, s)

    def body(x_blk, w_full):
        # w_full: [K, N/s] local; x_blk: [M, K/s] — this device's K panel.
        idx = jax.lax.axis_index(axis)
        kb = x_blk.shape[-1]

        def step(c, _):
            acc, blk, i = c
            src = (idx - i) % s                 # whose K-panel we now hold
            wk = jax.lax.dynamic_slice_in_dim(w_full, src * kb, kb, axis=0)
            nxt = jax.lax.ppermute(blk, axis, perm)   # prefetch next panel
            acc = acc + jnp.dot(blk, wk,
                                preferred_element_type=jnp.float32)
            return (acc, nxt, i + 1), None

        acc0 = jnp.zeros(x_blk.shape[:-1] + (w_full.shape[-1],),
                         jnp.float32)
        (acc, _, _), _ = jax.lax.scan(step, (acc0, x_blk, 0), None,
                                      length=s)
        return acc.astype(x_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis)),   # x: K-shard, w: N-shard
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)


def matmul_rs(x, w, *, mesh: Mesh, axis: str = "model"):
    """y = reduce_scatter(x @ w, N-axis), overlapped (row-parallel layer).

    x: [M, K/s] sharded over `axis` on K; w: [K/s, N] sharded on K.
    Each device owns partial sums for ALL of N; the ring rotates the
    accumulator so each hop adds the local contribution for the panel
    that will finally land on its owner — transfer of panel j overlaps
    compute of panel j+1.  Output: [M, N/s].
    """
    s = mesh.shape[axis]
    perm = _ring(axis, s)

    def body(x_blk, w_blk):
        idx = jax.lax.axis_index(axis)
        nb = w_blk.shape[-1] // s

        def wpanel(j):
            return jax.lax.dynamic_slice_in_dim(w_blk, j * nb, nb, axis=1)

        def step(c, _):
            acc, i = c
            # the accumulator held at scan step i still needs (s-1-i)
            # forward hops, so its final owner — whose panel we add — is
            # idx + (s-1-i) ≡ idx - 1 - i (mod s)
            j = (idx - 1 - i) % s
            acc = acc + jnp.dot(x_blk, wpanel(j),
                                preferred_element_type=jnp.float32)
            acc = jax.lax.ppermute(acc, axis, perm)
            return (acc, i + 1), None

        acc0 = jnp.zeros((x_blk.shape[0], nb), jnp.float32)
        (acc, _), _ = jax.lax.scan(step, (acc0, 0), None, length=s - 1)
        acc = acc + jnp.dot(x_blk, wpanel(idx),
                            preferred_element_type=jnp.float32)
        return acc.astype(x_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)


def psum_bf16(x, axis: str):
    """Gradient-compression all-reduce: bf16 on the wire, fp32 result.

    Halves cross-pod (DCN) gradient-sync bytes; the fp32 master update in
    the optimizer keeps convergence (EXPERIMENTS.md §Perf records the
    collective-term delta).
    """
    return jax.lax.psum(x.astype(jnp.bfloat16), axis).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mesh_axes",))
def _noop(x, mesh_axes=None):
    return x
