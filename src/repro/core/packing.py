"""Weight pre-packing — paper lever 2, as a first-class feature.

On the M1 the pack is a physical re-layout of B into [Kc, Nc] panels, paid
once at model load and amortized to zero across every prefill/decode call.
On TPU the per-call costs a stateless GEMM pays are the analogues we remove:

  * transpose        — engines store W as [N, K] (llama.cpp convention);
                       the kernel wants [K, N].  Done once here.
  * block padding    — pad (K, N) up to (block_k, block_n) multiples so the
                       kernel's BlockSpec grid divides exactly.  Once.
  * dtype cast       — e.g. fp32 master → bf16 compute copy.  Once.
  * device layout /  — place the packed array with the exact NamedSharding
    resharding         the GEMM consumes, so no relayout or resharding
                       collective appears in the per-step HLO.  Once.

``PackedWeight`` is a pytree, so it flows through jit/pjit/scan/checkpoint
like any array.  The stateless baseline (pack-every-call) is a plan
decision (``gemm.plan(..., pack=PACK_PERCALL)``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import panel_gemm as _kernel
from repro.obs import spans as _spans


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedWeight:
    """A weight packed once at load for the panel GEMM.

    data: [K_pad, N_pad] row-major, zero-padded to block multiples.
    n, k: logical (unpadded) dims.  block_n/block_k: the pack granularity.

    A *fused* pack (``pack_fused``) concatenates several same-K weights
    along N, each part individually padded to a ``block_n`` multiple so no
    kernel column tile straddles two parts.  ``n_splits`` is the static
    split map — the parts' LOGICAL widths, in order; for a fused pack
    ``n`` is the kernel-visible concatenated width (interior zero padding
    included).  ``n_splits == ()`` marks an ordinary single-weight pack.

    A *grouped* pack (``pack_grouped``, ``groups > 0``) holds a bank of
    ``groups`` same-shape weights as data ``[groups, K_pad, N_pad]``
    (MoE experts), each packed as above; the grouped GEMM arm runs it
    over row groups of varying size.
    """
    data: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    block_n: int = dataclasses.field(metadata=dict(static=True))
    block_k: int = dataclasses.field(metadata=dict(static=True))
    n_splits: tuple = dataclasses.field(default=(),
                                        metadata=dict(static=True))
    groups: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def shape(self):  # logical shape (fused: padded-concat width)
        return (self.k, self.n)

    @property
    def dtype(self):
        return self.data.dtype


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StackedLayer:
    """Layer ``layer`` of a stacked pack (every array of ``pack`` leading
    ``[L, ...]``): what the layer scan hands a projection instead of a
    slice, so the GEMM can read that layer's panels in place
    (``gemm.execute(..., layer=)``)."""
    pack: PackedWeight
    layer: jax.Array


def _pad_to(x: jax.Array, mults: tuple[int, int]) -> jax.Array:
    pk = (-x.shape[0]) % mults[0]
    pn = (-x.shape[1]) % mults[1]
    if pk or pn:
        x = jnp.pad(x, ((0, pk), (0, pn)))
    return x


def fit_block(dim: int, want: int, lane: int = 128) -> int:
    """Largest block <= ``want`` that divides dim rounded up to a lane
    multiple — keeps pack padding minimal on odd dims (hymba's 1600-wide
    projections would otherwise pad 28% to honor the deep default K
    block; the deep block only pays off when it divides anyway)."""
    padded = max(lane, ((dim + lane - 1) // lane) * lane)
    b = min(want, padded)
    while b > lane and padded % b:
        b //= 2
    return b if padded % b == 0 else lane


def pack(
    w: jax.Array,
    *,
    transposed: bool = False,          # True: w given as [N, K] (llama.cpp)
    block_n: int = _kernel.DEFAULT_BLOCK_N,
    block_k: int = _kernel.DEFAULT_BLOCK_K,
    dtype: Any = None,
    sharding: jax.sharding.Sharding | None = None,
    quant: str | None = None,
    sparse: bool | None = None,
) -> PackedWeight:
    """Pack a weight once at model load (see module docstring).

    ``quant`` ("int8" | "ternary") additionally QUANTIZES at pack time —
    the pre-pack lever extended below fp32 (repro.quant): the returned
    :class:`~repro.quant.QuantizedPackedWeight` carries codes + scales,
    the plan carries the format, and execute() streams 4x/16x fewer
    weight bytes per tile through the dequant-fused kernel.  The error
    ledger measures and tolerance-gates every concrete quantized pack
    (docs/quantization.md).  ``sparse`` (ternary only) controls the
    compressed zero-group layout: ``None`` auto-compresses when the
    pack's zero-group fraction clears ``quant.SPARSE_DENSITY_THRESHOLD``,
    ``True`` forces it, ``False`` keeps the dense layout."""
    with _spans.span("pack", n=int(w.shape[-1] if not transposed
                                   else w.shape[-2]),
                     k=int(w.shape[-2] if not transposed
                           else w.shape[-1]),
                     quant=quant or "fp32") as sp:
        if quant is not None:
            from repro.quant.formats import quantize_pack
            if dtype is not None:
                raise ValueError("dtype casts do not compose with quant= "
                                 "(codes have a fixed storage type)")
            return quantize_pack(w, quant, transposed=transposed,
                                 block_n=block_n, block_k=block_k,
                                 sharding=sharding, sparse=sparse)
        if sparse:
            raise ValueError("sparse= is a ternary pack-time lever; it "
                             "requires quant='ternary'")
        if transposed:
            n, k = w.shape
            w = w.T
        else:
            k, n = w.shape
        if dtype is not None:
            w = w.astype(dtype)
        block_k = fit_block(k, block_k)
        block_n = fit_block(n, block_n)
        sp.set(block_n=block_n, block_k=block_k)
        w = _pad_to(w, (block_k, block_n))
        if sharding is not None:
            w = jax.device_put(w, sharding)
        return PackedWeight(data=w, n=n, k=k, block_n=block_n,
                            block_k=block_k)


def pack_fused(
    parts,                             # sequence of [K, Ni] (or [Ni, K])
    *,
    transposed: bool = False,
    block_n: int = _kernel.DEFAULT_BLOCK_N,
    block_k: int = _kernel.DEFAULT_BLOCK_K,
    dtype: Any = None,
    sharding: jax.sharding.Sharding | None = None,
    quant: str | None = None,
    sparse: bool | None = None,
) -> PackedWeight:
    """Horizontally fuse same-input weights into ONE pack (paper lever 2
    applied across projections): concatenate along N at load, so one
    kernel pass streams the shared activations once and produces every
    part (QKV; gate+up for the glu epilogue).

    Each part is padded to a ``block_n`` multiple before the concat —
    column tiles never straddle parts, which is what lets (a) the output
    split map stay static (``gemm.split_fused``) and (b) the glu kernel
    address gate/up halves by tile offset.  Parts may also be stacked
    ``[L, K, Ni]`` (scan-over-layers weights); the leading dim rides
    through untouched.  ``quant`` quantizes every part at pack time
    (per-part per-column scales — see ``pack(quant=)``).
    """
    if quant is not None:
        from repro.quant.formats import quantize_pack_fused
        if dtype is not None:
            raise ValueError("dtype casts do not compose with quant=")
        with _spans.span("pack_fused", parts=len(parts),
                         quant=quant):
            return quantize_pack_fused(parts, quant,
                                       transposed=transposed,
                                       block_n=block_n, block_k=block_k,
                                       sharding=sharding, sparse=sparse)
    if sparse:
        raise ValueError("sparse= is a ternary pack-time lever; it "
                         "requires quant='ternary'")
    with _spans.span("pack_fused", parts=len(parts), quant="fp32"):
        return _pack_fused_fp32(parts, transposed=transposed,
                                block_n=block_n, block_k=block_k,
                                dtype=dtype, sharding=sharding)


def _pack_fused_fp32(parts, *, transposed, block_n, block_k, dtype,
                     sharding) -> PackedWeight:
    ws = [jnp.swapaxes(w, -1, -2) if transposed else w for w in parts]
    if len(ws) < 2:
        raise ValueError("pack_fused needs at least two weights; "
                         "use pack() for one")
    k = ws[0].shape[-2]
    if any(w.shape[-2] != k or w.ndim != ws[0].ndim for w in ws):
        raise ValueError(
            f"fused parts must share K and rank; got "
            f"{[tuple(w.shape) for w in ws]}")
    if dtype is not None:
        ws = [w.astype(dtype) for w in ws]
    block_k = fit_block(k, block_k)
    bn = min(fit_block(w.shape[-1], block_n) for w in ws)
    n_splits = tuple(int(w.shape[-1]) for w in ws)
    pk = (-k) % block_k

    def pad(w):
        pn = (-w.shape[-1]) % bn
        cfg = [(0, 0)] * (w.ndim - 2) + [(0, pk), (0, pn)]
        return jnp.pad(w, cfg) if pk or pn else w

    data = jnp.concatenate([pad(w) for w in ws], axis=-1)
    if sharding is not None:
        data = jax.device_put(data, sharding)
    return PackedWeight(data=data, n=int(data.shape[-1]), k=k,
                        block_n=bn, block_k=block_k, n_splits=n_splits)


def pack_grouped(parts, *, block_n: int = _kernel.DEFAULT_BLOCK_N,
                 block_k: int = _kernel.DEFAULT_BLOCK_K) -> PackedWeight:
    """Pack a bank of weights ``[..., G, K, N]`` once at load for the
    grouped GEMM arm: one part, or several same-K parts fused along N as
    ``pack_fused`` fuses them (an expert's gate and up for the glu
    epilogue).  Leading dims (the scan's layers) ride through; blocks fit
    the parts' widths (``fit_block``) so the bank is padded as little as
    the panel grid allows."""
    parts = list(parts)
    with _spans.span("pack_grouped", parts=len(parts)):
        if len(parts) > 1:
            pw = _pack_fused_fp32(parts, transposed=False, block_n=block_n,
                                  block_k=block_k, dtype=None,
                                  sharding=None)
        else:
            w = parts[0]
            k, n = w.shape[-2:]
            bk, bn = fit_block(k, block_k), fit_block(n, block_n)
            pads = [(0, 0)] * (w.ndim - 2) + [(0, (-k) % bk), (0, (-n) % bn)]
            pw = PackedWeight(data=jnp.pad(w, pads), n=n, k=k, block_n=bn,
                              block_k=bk)
        return dataclasses.replace(pw, groups=int(pw.data.shape[-3]))


def pack_percall(w: jax.Array, *, transposed: bool, block_n: int,
                 block_k: int, dtype: Any = None) -> jax.Array:
    """The stateless pack, traced INSIDE the per-call GEMM (the honest
    cblas_sgemm/BNNSMatMul analogue: transpose + pad paid on every call)."""
    if transposed:
        w = w.T
    if dtype is not None:
        w = w.astype(dtype)
    return _pad_to(w, (block_k, block_n))
