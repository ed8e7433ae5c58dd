"""Dequant-fused panel GEMM — the quantized formats' compute loop.

Same Goto-style (block_m, block_n, block_k) panel schedule, Z-discipline
and fused-epilogue store step as ``kernels/panel_gemm``, with ONE change
in the streamed operand: the weight tile arrives as int8 codes (or 2-bit
packed ternary bytes) plus a per-column scale row, is dequantized into
registers (`codes -> fp32 * scale`), and feeds the same fp32 MXU
accumulation.  The tile's HBM->VMEM traffic shrinks 4x (int8) / 16x
(ternary) while the accumulation semantics stay those of the fp32
kernel on the dequantized panels — which is exactly the contract the
structural gate below enforces bitwise.

Every ``EpilogueSpec`` composes: the store step applies bias /
activation / softcap / residual (and the glu two-accumulator combine)
on the fp32 accumulator through the SAME shared ``apply_epilogue`` /
``apply_epilogue_glu`` definitions, so fused-quant == unfused-quant
holds bit-identically just like the fp32 path.

The interpret-mode oracle: ``quant_panel_gemm(interpret=True)`` must be
BIT-IDENTICAL to ``ref.gemm_blocked(x, dequantize_padded(...),
block_k)`` (+ the jnp epilogue under jit) — dequantization is
elementwise identical tiled or whole, so the only degree of freedom
left is the K accumulation order, which the blocked oracle pins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.panel_gemm import (DECODE_BLOCK_M, DEFAULT_BLOCK_K,
                                      DEFAULT_BLOCK_M, DEFAULT_BLOCK_N,
                                      EpilogueSpec, _act_fn, _finish,
                                      apply_epilogue, apply_epilogue_glu,
                                      gate_interpret, splitk_combine)
from repro.quant import formats as F


def _dequant_tile(w_vals, s_vals, fmt: str) -> jax.Array:
    """codes tile -> fp32 weight tile (the in-registers dequant).
    ``s_vals`` is the tile's ``[block_k // GROUP_K, block_n]`` scale
    slab (tiles never straddle a group, so the slab is exact).
    Elementwise identical to ``formats.dequantize_padded`` on the full
    array — the bitwise contract with the blocked oracle depends on it,
    so both route through the same unpack/cast/expand/multiply ops."""
    if fmt == "ternary":
        codes = F.unpack_ternary_codes(w_vals)
    else:
        codes = w_vals.astype(jnp.float32)
    return codes * F.expand_scales(s_vals, codes.shape[-2])


def _scale_slabs(scales, n_tiles: int, rows: int, n: int) -> jax.Array:
    """``[K_pad // GROUP_K, N]`` scales as ``[n_tiles, rows, N]``: one
    slab of ``rows`` scale rows per K tile.  A BlockSpec of
    ``(None, rows, block_n)`` over it spans the whole second-to-last
    dim, which Mosaic's (8, 128) block tiling accepts for any ``rows``;
    a ``(rows, block_n)`` block of the 2-D array would not when
    ``rows`` (``block_k // GROUP_K``) is not a multiple of 8."""
    return scales.reshape(n_tiles, rows, n).astype(jnp.float32)


def _quant_gemm_kernel(x_ref, w_ref, s_ref, *refs, nk: int, fmt: str,
                       spec: EpilogueSpec | None = None):
    """One (i, j, k) grid step: acc += x @ dequant(codes, scale)."""
    refs = list(refs)
    acc_ref = refs.pop()
    o_ref = refs.pop()
    bias_ref = refs.pop(0) if spec is not None and spec.bias else None
    res_ref = refs.pop(0) if spec is not None and spec.residual else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_tile(w_ref[...], s_ref[...], fmt)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        acc = acc_ref[...]
        if spec is not None:
            if spec.bias:
                acc = acc + bias_ref[...]
            if spec.act is not None:
                acc = _act_fn(spec.act)(acc)
            acc = _finish(spec, acc, res_ref[...] if res_ref is not None
                          else None)
        o_ref[...] = acc.astype(o_ref.dtype)


def _quant_glu_kernel(x_ref, wg_ref, wu_ref, sg_ref, su_ref, *refs,
                      nk: int, fmt: str, spec: EpilogueSpec):
    """GLU variant: gate/up column panels of one quantized fused pack,
    each dequantized into registers, two fp32 accumulators over the K
    grid, ``act(gate) * up`` combined in the store step."""
    refs = list(refs)
    acc_u_ref = refs.pop()
    acc_g_ref = refs.pop()
    o_ref = refs.pop()
    bg_ref = refs.pop(0) if spec.bias else None
    bu_ref = refs.pop(0) if spec.bias else None
    res_ref = refs.pop(0) if spec.residual else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_g_ref[...] = jnp.zeros_like(acc_g_ref)
        acc_u_ref[...] = jnp.zeros_like(acc_u_ref)

    x = x_ref[...]
    acc_g_ref[...] += jnp.dot(
        x, _dequant_tile(wg_ref[...], sg_ref[...], fmt),
        preferred_element_type=jnp.float32)
    acc_u_ref[...] += jnp.dot(
        x, _dequant_tile(wu_ref[...], su_ref[...], fmt),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        acc = apply_epilogue_glu(
            acc_g_ref[...], acc_u_ref[...], spec,
            bias_g=bg_ref[...] if bg_ref is not None else None,
            bias_u=bu_ref[...] if bu_ref is not None else None,
            residual=res_ref[...] if res_ref is not None else None)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("weight_format", "block_m", "block_n", "block_k",
                     "interpret", "out_dtype", "epilogue"),
)
def quant_panel_gemm(
    x: jax.Array,               # [M_pad, K_pad] activations (pre-padded)
    data: jax.Array,            # codes: [K_pad, N_pad] int8 or
                                #        [K_pad // 4, N_pad] uint8 ternary
    scales: jax.Array,          # [K_pad // GROUP_K, N_pad] fp32 scales
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    weight_format: str,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=None,
    epilogue: EpilogueSpec | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(x @ dequant(data, scales)) via dequant-fused tiles."""
    fmt = weight_format
    if fmt not in F.FORMATS:
        raise ValueError(f"unknown weight_format {fmt!r}")
    kdiv = 4 if fmt == "ternary" else 1
    m, k = x.shape
    krows, n = data.shape
    assert k == krows * kdiv, (
        f"contraction mismatch: x K={k} vs codes K={krows * kdiv}")
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{n},{k}) not aligned to blocks "
        f"({block_m},{block_n},{block_k}); pack first")
    assert block_k % kdiv == 0
    assert block_k % F.GROUP_K == 0, (
        f"block_k={block_k} must span whole GROUP_K={F.GROUP_K} scale "
        f"groups (tiles never straddle a group)")
    nk = k // block_k
    wbk = block_k // kdiv               # codes-row depth of one K tile
    out_dtype = out_dtype or x.dtype
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    glu = spec is not None and spec.glu is not None
    n_out = n // 2 if glu else n
    if glu:
        assert n % 2 == 0 and n_out % block_n == 0, (
            f"glu epilogue needs block-aligned column halves; got N={n} "
            f"with block_n={block_n} — pack with quantize_pack_fused")
    assert (bias is not None) == bool(spec is not None and spec.bias)
    assert (residual is not None) == bool(spec is not None and spec.residual)

    sbk = block_k // F.GROUP_K          # scale rows per K tile
    assert scales.shape[-2:] == (k // F.GROUP_K, n), (
        f"scales {scales.shape} vs expected ({k // F.GROUP_K},{n})")
    s3 = _scale_slabs(scales, nk, sbk, n)
    half_tiles = n_out // block_n
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((wbk, block_n), lambda i, j, kk: (kk, j))
    s_spec = pl.BlockSpec((None, sbk, block_n), lambda i, j, kk: (kk, 0, j))
    if glu:      # up panel + its scale slab: column-offset index maps
        ops = [x, data, data, s3, s3]
        in_specs = [
            x_spec, w_spec,
            pl.BlockSpec((wbk, block_n),
                         lambda i, j, kk: (kk, j + half_tiles)),
            s_spec,
            pl.BlockSpec((None, sbk, block_n),
                         lambda i, j, kk: (kk, 0, j + half_tiles)),
        ]
    else:
        ops = [x, data, s3]
        in_specs = [x_spec, w_spec, s_spec]
    if spec is not None and spec.bias:
        b2 = bias.reshape(1, n).astype(jnp.float32)
        ops.append(b2)
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        if glu:
            ops.append(b2)
            in_specs.append(pl.BlockSpec(
                (1, block_n), lambda i, j, kk: (0, j + half_tiles)))
    if spec is not None and spec.residual:
        assert residual.shape == (m, n_out), (
            f"residual {residual.shape} vs output ({m},{n_out})")
        ops.append(residual.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, j, kk: (i, j)))

    if glu:
        kernel = functools.partial(_quant_glu_kernel, nk=nk, fmt=fmt,
                                   spec=spec)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((block_m, block_n), jnp.float32)]
    else:
        kernel = functools.partial(_quant_gemm_kernel, nk=nk, fmt=fmt,
                                   spec=spec)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    return pl.pallas_call(
        kernel,
        name="quant_panel_gemm_glu" if glu else "quant_panel_gemm",
        grid=(m // block_m, n_out // block_n, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*ops)


# ------------------------------------------------- sparse ternary lane
def _sparse_layout_arrays(sparse_layout):
    """Group-walk constants from a pack's static ``sparse_layout``
    descriptor: ``gidx`` int32 ``[occ]`` (compacted slot -> original
    group id, the x index map's lookup) and ``occ_mat`` int32
    ``[n_blocks, occ]`` (per-column-panel occupancy of each slot, the
    kernel's skip predicate).  Static per pack, so they bake into the
    jitted call as constants — no scalar prefetch machinery needed."""
    import numpy as np
    k_groups, group_index, occ_bitmap, _bn = sparse_layout
    gidx = np.asarray(group_index, np.int32).reshape(-1)
    occ = np.zeros((len(occ_bitmap), len(group_index)), np.int32)
    for b, bits in enumerate(occ_bitmap):
        for s, g in enumerate(group_index):
            occ[b, s] = (bits >> int(g)) & 1
    return gidx, occ


def _sparse_gemm_kernel(gidx_ref, occ_ref, x_ref, w_ref, s_ref, *refs,
                        ns: int, spec: EpilogueSpec | None = None):
    """One (i, j, s) grid step of the sparse walk: slot ``s`` is the
    s-th OCCUPIED group (union over column panels — ``gidx_ref`` holds
    its original K offset, consumed by the x index map); the accumulate
    is additionally predicated on this column panel's own occupancy
    (``occ_ref[j, s]``), so each panel touches only its nonzero groups.
    Skipping a group is bitwise identical to the dense kernel adding its
    all-zero product tile (fp32 ``acc + (+0.0)`` preserves ``acc``), so
    the dense Z-discipline contract carries over unchanged."""
    del gidx_ref
    refs = list(refs)
    acc_ref = refs.pop()
    o_ref = refs.pop()
    bias_ref = refs.pop(0) if spec is not None and spec.bias else None
    res_ref = refs.pop(0) if spec is not None and spec.residual else None
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[j, s] != 0)
    def _accum():
        w = _dequant_tile(w_ref[...], s_ref[...], "ternary")
        acc_ref[...] += jnp.dot(x_ref[...], w,
                                preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _store():
        acc = acc_ref[...]
        if spec is not None:
            if spec.bias:
                acc = acc + bias_ref[...]
            if spec.act is not None:
                acc = _act_fn(spec.act)(acc)
            acc = _finish(spec, acc, res_ref[...] if res_ref is not None
                          else None)
        o_ref[...] = acc.astype(o_ref.dtype)


def _sparse_glu_kernel(gidx_ref, occ_ref, x_ref, wg_ref, wu_ref, sg_ref,
                       su_ref, *refs, ns: int, half_tiles: int,
                       spec: EpilogueSpec):
    """GLU variant of the sparse walk: the gate and up column panels
    carry separate occupancy columns of the bitmap, so each half skips
    its own zero groups independently."""
    del gidx_ref
    refs = list(refs)
    acc_u_ref = refs.pop()
    acc_g_ref = refs.pop()
    o_ref = refs.pop()
    bg_ref = refs.pop(0) if spec.bias else None
    bu_ref = refs.pop(0) if spec.bias else None
    res_ref = refs.pop(0) if spec.residual else None
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_g_ref[...] = jnp.zeros_like(acc_g_ref)
        acc_u_ref[...] = jnp.zeros_like(acc_u_ref)

    x = x_ref[...]

    @pl.when(occ_ref[j, s] != 0)
    def _accum_g():
        acc_g_ref[...] += jnp.dot(
            x, _dequant_tile(wg_ref[...], sg_ref[...], "ternary"),
            preferred_element_type=jnp.float32)

    @pl.when(occ_ref[j + half_tiles, s] != 0)
    def _accum_u():
        acc_u_ref[...] += jnp.dot(
            x, _dequant_tile(wu_ref[...], su_ref[...], "ternary"),
            preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _store():
        acc = apply_epilogue_glu(
            acc_g_ref[...], acc_u_ref[...], spec,
            bias_g=bg_ref[...] if bg_ref is not None else None,
            bias_u=bu_ref[...] if bu_ref is not None else None,
            residual=res_ref[...] if res_ref is not None else None)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sparse_layout", "block_m", "block_n", "interpret",
                     "out_dtype", "epilogue"),
)
def sparse_quant_panel_gemm(
    x: jax.Array,               # [M_pad, K_pad] — padded to the LOGICAL K
    data: jax.Array,            # [occ * GROUP_K // 4, N_pad] uint8 codes
    scales: jax.Array,          # [occ, N_pad] fp32 survivor scale rows
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    sparse_layout: tuple,       # SparseTernaryPackedWeight.sparse_layout
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    out_dtype=None,
    epilogue: EpilogueSpec | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(x @ dequant(compressed codes)) — the sparse walk.

    The K grid runs over the ``occ`` compacted slots (one ``GROUP_K``
    group per step, NOT the plan's ``block_k``: the compressed layout is
    group-granular by construction), and each column panel's accumulate
    is predicated on its occupancy bit.  The activations arrive padded
    to the LOGICAL ``K_pad``; the x index map jumps to each surviving
    group's original K offset via the baked-in ``gidx`` table.

    Bitwise contract: identical to ``quant_panel_gemm(block_k=GROUP_K)``
    on the decompressed codes (and hence, transitively, to
    ``ref.gemm_blocked`` at ``GROUP_K``) — the structural gate below
    asserts both.
    """
    k_groups, group_index, _occ_bitmap, pack_bn = sparse_layout
    assert block_n == pack_bn, (
        f"sparse occupancy is per pack column block: kernel block_n="
        f"{block_n} must equal the pack's block_n={pack_bn}")
    rpg = F.GROUP_K // 4
    m, k = x.shape
    rows, n = data.shape
    ns = len(group_index)
    assert k == k_groups * F.GROUP_K, (
        f"x K={k} vs logical padded K={k_groups * F.GROUP_K} "
        f"(pad activations to the LOGICAL depth, not the compacted one)")
    assert rows == ns * rpg, (
        f"compacted codes rows {rows} vs {ns} occupied groups x {rpg}")
    assert m % block_m == 0 and n % block_n == 0, (
        f"shapes ({m},{n}) not aligned to blocks ({block_m},{block_n})")
    assert scales.shape[-2:] == (ns, n), (
        f"scales {scales.shape} vs expected ({ns},{n})")
    out_dtype = out_dtype or x.dtype
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    glu = spec is not None and spec.glu is not None
    n_out = n // 2 if glu else n
    if glu:
        assert n % 2 == 0 and n_out % block_n == 0, (
            f"glu epilogue needs block-aligned column halves; got N={n} "
            f"with block_n={block_n} — pack with quantize_pack_fused")
    assert (bias is not None) == bool(spec is not None and spec.bias)
    assert (residual is not None) == bool(spec is not None
                                          and spec.residual)

    if ns == 0:
        # fully-zero weight: the Z-discipline result is a zero
        # accumulator through the shared jnp epilogue (full width —
        # apply_epilogue splits the glu halves itself)
        z = jnp.zeros((m, n), jnp.float32)
        if spec is not None:
            z = apply_epilogue(z, spec, bias=bias, residual=residual)
        return z[:, :n_out].astype(out_dtype)

    gidx, occ_mat = _sparse_layout_arrays(sparse_layout)
    s3 = _scale_slabs(scales, ns, 1, n)
    half_tiles = n_out // block_n
    # the group-walk tables ride in as SCALAR-PREFETCH operands (index
    # maps may not capture array constants): every index map receives
    # (i, j, s, gidx_ref, occ_ref) and the x map jumps to slot s's
    # original group offset
    x_spec = pl.BlockSpec((block_m, F.GROUP_K),
                          lambda i, j, s, gidx, occ: (i, gidx[s]))
    w_spec = pl.BlockSpec((rpg, block_n),
                          lambda i, j, s, gidx, occ: (s, j))
    s_spec = pl.BlockSpec((None, 1, block_n),
                          lambda i, j, s, gidx, occ: (s, 0, j))
    if glu:
        ops = [x, data, data, s3, s3]
        in_specs = [
            x_spec, w_spec,
            pl.BlockSpec((rpg, block_n),
                         lambda i, j, s, gidx, occ: (s, j + half_tiles)),
            s_spec,
            pl.BlockSpec((None, 1, block_n),
                         lambda i, j, s, gidx, occ: (s, 0, j + half_tiles)),
        ]
    else:
        ops = [x, data, s3]
        in_specs = [x_spec, w_spec, s_spec]
    if spec is not None and spec.bias:
        b2 = bias.reshape(1, n).astype(jnp.float32)
        ops.append(b2)
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, s, gidx, occ: (0, j)))
        if glu:
            ops.append(b2)
            in_specs.append(pl.BlockSpec(
                (1, block_n),
                lambda i, j, s, gidx, occ: (0, j + half_tiles)))
    if spec is not None and spec.residual:
        assert residual.shape == (m, n_out), (
            f"residual {residual.shape} vs output ({m},{n_out})")
        ops.append(residual.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, j, s, gidx, occ: (i, j)))

    if glu:
        kernel = functools.partial(_sparse_glu_kernel, ns=ns,
                                   half_tiles=half_tiles, spec=spec)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((block_m, block_n), jnp.float32)]
    else:
        kernel = functools.partial(_sparse_gemm_kernel, ns=ns, spec=spec)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // block_m, n_out // block_n, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, s, gidx, occ: (i, j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        name=("sparse_quant_panel_gemm_glu" if glu
              else "sparse_quant_panel_gemm"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(gidx), jnp.asarray(occ_mat), *ops)


def sparse_ref(x, spw, *, epilogue=None, bias=None, residual=None):
    """The sparse lane's oracle: ``ref.gemm_blocked`` at ``GROUP_K``
    over the DECOMPRESSED panels + the shared jnp epilogue — the dense
    contract's oracle evaluated on the layout round-trip, so sparse
    correctness never re-derives a tolerance."""
    from repro.kernels import ref
    deq = F.dequantize(spw)     # decompresses first
    acc = ref.gemm_blocked(x, deq, F.GROUP_K, out_dtype=jnp.float32)
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    if spec is None:
        return acc
    return jax.jit(
        lambda a, b, r: apply_epilogue(
            a, spec, bias=b, residual=r).astype(jnp.float32)
    )(acc, bias, residual)


# ----------------------------------------------------------- split-K lane
def _quant_splitk_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                         nks: int, fmt: str):
    """One (s, i, j, kk) grid step of the quantized split-K partials
    pass: the K-slice's codes+scales tile dequantizes into registers and
    accumulates the slice's fp32 partial — the decode lane's
    reduction-side occupancy with the 4x/16x tile-byte reduction decode
    most needs (weight bytes dominate at M <= 8)."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_tile(w_ref[...], s_ref[...], fmt)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(kk == nks - 1)
    def _store():
        o_ref[...] = acc_ref[...][None]


@functools.partial(
    jax.jit,
    static_argnames=("weight_format", "split_k", "block_m", "block_n",
                     "block_k", "interpret", "out_dtype", "epilogue"),
)
def quant_panel_gemm_splitk(
    x: jax.Array,
    data: jax.Array,
    scales: jax.Array,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    weight_format: str,
    split_k: int,
    block_m: int = DECODE_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=None,
    epilogue: EpilogueSpec | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(splitk_combine(per-slice x @ dequant(codes, scales))).

    The dequant-fused analogue of ``panel_gemm_splitk``: grid
    ``(s, i, j, kk)`` with per-slice fp32 partials, combined by the
    shared deterministic tree and finished by the shared jnp epilogue.
    Bit-identical to ``ref.gemm_splitk`` over the dequantized panels at
    the same ``(block_k, split_k)`` — the structural gate below."""
    fmt = weight_format
    if fmt not in F.FORMATS:
        raise ValueError(f"unknown weight_format {fmt!r}")
    kdiv = 4 if fmt == "ternary" else 1
    m, k = x.shape
    krows, n = data.shape
    assert k == krows * kdiv, (
        f"contraction mismatch: x K={k} vs codes K={krows * kdiv}")
    assert split_k >= 1 and k % split_k == 0, (
        f"K={k} not divisible by split_k={split_k}")
    ks = k // split_k
    assert m % block_m == 0 and n % block_n == 0 and ks % block_k == 0, (
        f"shapes ({m},{n},{k}) / slice depth {ks} not aligned to blocks "
        f"({block_m},{block_n},{block_k}); pack first")
    assert block_k % kdiv == 0
    assert block_k % F.GROUP_K == 0, (
        f"block_k={block_k} must span whole GROUP_K={F.GROUP_K} scale "
        f"groups (tiles never straddle a group)")
    nks = ks // block_k
    wbk = block_k // kdiv
    sbk = block_k // F.GROUP_K
    out_dtype = out_dtype or x.dtype
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    glu = spec is not None and spec.glu is not None
    n_out = n // 2 if glu else n
    if glu:
        assert n % 2 == 0 and n_out % block_n == 0, (
            f"glu epilogue needs block-aligned column halves; got N={n} "
            f"with block_n={block_n} — pack with quantize_pack_fused")
    assert (bias is not None) == bool(spec is not None and spec.bias)
    assert (residual is not None) == bool(spec is not None
                                          and spec.residual)
    assert scales.shape[-2:] == (k // F.GROUP_K, n), (
        f"scales {scales.shape} vs expected ({k // F.GROUP_K},{n})")
    s3 = _scale_slabs(scales, split_k * nks, sbk, n)

    partials = pl.pallas_call(
        functools.partial(_quant_splitk_kernel, nks=nks, fmt=fmt),
        name="quant_panel_gemm_splitk",
        grid=(split_k, m // block_m, n // block_n, nks),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda s, i, j, kk: (i, s * nks + kk)),
            pl.BlockSpec((wbk, block_n),
                         lambda s, i, j, kk: (s * nks + kk, j)),
            pl.BlockSpec((None, sbk, block_n),
                         lambda s, i, j, kk: (s * nks + kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda s, i, j, kk: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((split_k, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(x, data, s3)
    acc = splitk_combine(partials)
    if spec is not None:
        acc = apply_epilogue(acc, spec, bias=bias, residual=residual)
    return acc.astype(out_dtype)


# --------------------------------------------------- structural gate
_gate_memo: dict[tuple, bool] = {}


def quant_gate(bm: int, bn: int, bk: int, fmt: str, *,
               epilogue: EpilogueSpec | None = None,
               reduced_k_blocks: int = 2, seed: int = 0,
               split_k: int = 1, sparse: bool = False) -> bool:
    """The autotune reject protocol for a quantized block triple: the
    dequant-fused kernel (compiled on a TPU, interpreted elsewhere —
    ``gate_interpret``) on a reduced shape with a real K-carry must be
    BIT-IDENTICAL to ``ref.gemm_blocked`` over the dequantized panels
    (+ the jnp epilogue under jit).  ``split_k > 1`` gates the decode
    lane's split-K variant against ``ref.gemm_splitk``
    over the same dequantized panels.  This attests the KERNEL (tiling,
    dequant placement, accumulation order); the format's numeric error
    vs fp32 is the error ledger's separate, tolerance-gated concern.

    ``sparse=True`` gates the compressed-ternary walk instead: on a
    reduced group-sparse weight (whole zero groups plus one group zeroed
    in only some column panels, exercising the per-panel skip), the
    sparse kernel must be bit-identical BOTH to the dense ternary kernel
    at ``block_k=GROUP_K`` on the same codes AND to ``sparse_ref`` (the
    blocked oracle over the decompressed layout).  The sparse walk is
    group-granular — it ignores the plan's ``block_k`` — so the sparse
    gate memoizes per (block_m, block_n, epilogue) only.
    """
    import numpy as np

    from repro.core import bitexact
    from repro.kernels import ref

    if sparse:
        if fmt != "ternary" or split_k != 1:
            return False            # the sparse lane is ternary, split_k=1
        bk = F.GROUP_K              # the walk's only K granularity
    key = (bm, bn, bk, fmt, epilogue, split_k, sparse)
    if key in _gate_memo:
        return _gate_memo[key]
    rng = np.random.default_rng(seed)
    interpret = gate_interpret()
    glu = epilogue is not None and epilogue.glu is not None
    if sparse:
        kg_r = 8
        k_r = kg_r * F.GROUP_K
        n_r = 2 * bn if glu else bn
        x = jnp.asarray(rng.standard_normal((bm, k_r)), jnp.float32)
        wf = rng.standard_normal((k_r, n_r))
        G = F.GROUP_K
        wf[1 * G:2 * G] = 0.0           # whole zero groups (compress away)
        wf[4 * G:5 * G] = 0.0
        wf[6 * G:7 * G, :bn] = 0.0      # panel-local zero (occupancy skip)
        w = jnp.asarray(wf, jnp.float32)
        dq = F.quantize_pack(w, "ternary", block_n=bn, block_k=F.GROUP_K,
                             sparse=False, measure=False)
        sq = F.compress_ternary(dq)
        bias = (jnp.asarray(rng.standard_normal((n_r,)), jnp.float32)
                if epilogue is not None and epilogue.bias else None)
        n_out = bn if glu else n_r
        res = (jnp.asarray(rng.standard_normal((bm, n_out)), jnp.float32)
               if epilogue is not None and epilogue.residual else None)
        y_s = sparse_quant_panel_gemm(
            x, sq.data, sq.scales, bias, res,
            sparse_layout=sq.sparse_layout, block_m=bm, block_n=bn,
            epilogue=epilogue, interpret=interpret)
        y_d = quant_panel_gemm(
            x, dq.data, dq.scales, bias, res, weight_format="ternary",
            block_m=bm, block_n=bn, block_k=F.GROUP_K,
            epilogue=epilogue, interpret=interpret)
        oracle = sparse_ref(x, sq, epilogue=epilogue, bias=bias,
                            residual=res)
        ok = (bitexact.bit_identical(np.asarray(y_s), np.asarray(y_d))
              and bitexact.bit_identical(np.asarray(y_s),
                                         np.asarray(oracle)))
        _gate_memo[key] = ok
        return ok
    m_r, k_r = bm, reduced_k_blocks * bk * split_k
    n_r = 2 * bn if glu else bn
    x = jnp.asarray(rng.standard_normal((m_r, k_r)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k_r, n_r)), jnp.float32)
    q, s = F.quantize(w, fmt)
    data = F.pack_ternary_codes(q) if fmt == "ternary" else q
    deq = F.dequantize_padded(data, s, fmt)
    bias = (jnp.asarray(rng.standard_normal((n_r,)), jnp.float32)
            if epilogue is not None and epilogue.bias else None)
    n_out = bn if glu else n_r
    res = (jnp.asarray(rng.standard_normal((m_r, n_out)), jnp.float32)
           if epilogue is not None and epilogue.residual else None)
    if split_k > 1:
        y = quant_panel_gemm_splitk(x, data, s, bias, res,
                                    weight_format=fmt, split_k=split_k,
                                    block_m=bm, block_n=bn, block_k=bk,
                                    epilogue=epilogue, interpret=interpret)
        acc = ref.gemm_splitk(x, deq, bk, split_k, out_dtype=jnp.float32)
    else:
        y = quant_panel_gemm(x, data, s, bias, res, weight_format=fmt,
                             block_m=bm, block_n=bn, block_k=bk,
                             epilogue=epilogue, interpret=interpret)
        acc = ref.gemm_blocked(x, deq, bk, out_dtype=jnp.float32)
    if epilogue is None:
        oracle = acc
    else:
        oracle = jax.jit(
            lambda a, b, r: apply_epilogue(
                a, epilogue, bias=b, residual=r).astype(jnp.float32)
        )(acc, bias, res)
    ok = bitexact.bit_identical(np.asarray(y), np.asarray(oracle))
    _gate_memo[key] = ok
    return ok
