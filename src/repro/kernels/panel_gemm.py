"""Panel GEMM — the paper's kernel, adapted to the TPU MXU.

Paper (M1 AMX)                      → here (TPU, Pallas)
------------------------------------------------------------------
Goto–BLIS 3-level blocking          → (block_m, block_n, block_k) BlockSpec
   A-slice sized to the 128 KB L1   →   blocks sized to fit ~16 MB VMEM,
                                        MXU-aligned (multiples of 128 lanes)
column panel width Nc               → block_n (grid granularity over N)
K-blocking depth Kc                 → block_k (grid depth over K)
skip-Z at (pc==0, kk==0)            → @pl.when(k == 0) zero-init of the
                                      fp32 VMEM accumulator
LDZ/STZ carry of Z across pc        → accumulator scratch carried across the
                                      innermost ("arbitrary") K grid dim;
                                      output written once at k == nk-1
4-way FMA32 ILP across Z banks      → the MXU consumes the whole (bm, bn)
                                      tile; ILP is the hardware's problem —
                                      exactly the paper's point: the inner
                                      loop is fixed, the levers are above it.

The kernel expects its B operand ALREADY in the packed layout produced by
``repro.core.packing`` ([K_pad, N_pad], row-major, block-aligned).  The
pack is paid once at model load (paper lever 2); this kernel is the
per-call "compute loop only" path.

Stacked packs, read in place: a model's per-layer weights live as one
stack ``[L, K_pad, N_pad]``, and the layer scan hands the kernel the
whole stack plus the layer's index (a scalar-prefetch operand that the
weight's ``BlockSpec`` index map reads), so the kernel DMAs only that
layer's panels and no per-layer slice is ever copied.  A 2-D pack is a
stack of one.  On the chip each tile enters the MXU in the dtype the
default matmul precision gives it (``mxu_operand_dtype``): fp32 panels
are read as stored and rounded to bf16 in VMEM, so a step reads the
pack's 4 B per parameter once and makes no bf16 copy of it.

Fused epilogue (the lever ABOVE the store): the baseline kernel flushes
the fp32 accumulator to HBM only for XLA to re-read it for bias /
activation / residual.  ``EpilogueSpec`` instead applies those ops on the
fp32 VMEM accumulator inside the ``k == nk-1`` store step (the STZ
analogue), so the tile leaves VMEM exactly once, already finished.  The
``glu`` variant carries TWO accumulators over the K grid — gate and up
column panels of a horizontally fused weight — and stores
``act(gate) * up``: one pass streams x once for both projections and the
[M, 2F] intermediate never exists in HBM.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Deployed block pair (the (Nc, Kc) analogue), fixed by the offline sweep
# in core/autotune.py under the bit-exactness gate (winner over the twelve
# paper shapes).  That sweep ranks by the scheduler model's predicted
# time; no chip measurement backs the pair yet (ROADMAP debt 4).  The
# deep K block mirrors the paper's Kc = 2,048 — affordable only because
# the weight is pre-packed (paper §3.3); packing.fit_block shrinks it per
# weight when K is not block-divisible.
DEFAULT_BLOCK_M = 128     # the paper's M = S = 128 prefill row panel
DEFAULT_BLOCK_N = 512     # column-panel width (lever-1 knob)
DEFAULT_BLOCK_K = 2048    # K-blocking depth (lever-2-unlocked knob)

# Skinny-M specialization for the decode fast lane: [slots, 1] decode
# rows pad to one 8-row sublane tile instead of the 128-row prefill
# panel (gemm.policy's decode arm plans against this).
DECODE_BLOCK_M = 8

# v5e VMEM budget the blocks must respect (bytes); checked by vmem_bytes().
VMEM_BUDGET = 16 * 1024 * 1024


def gate_interpret() -> bool:
    """Whether a bit-exactness gate runs its kernel in the Pallas
    interpreter.  On a TPU the gate compiles the kernel the plan will
    dispatch, so it attests what runs; elsewhere nothing can compile a
    Mosaic kernel and the interpreter stands in."""
    return jax.default_backend() != "tpu"


def mxu_operand_dtype(interpret: bool):
    """The dtype both operand tiles enter the MXU in: bf16 where the
    kernel is compiled (Mosaic, a TPU) and the matmul precision is the
    default, the operands XLA's default-precision dot gives the MXU; None
    (tiles as stored) in the interpreter, which keeps it bit-identical to
    ``kernels/ref.gemm_blocked``, and at any higher precision.  The cast
    is explicit because Mosaic lowers an fp32 dot as a multi-pass
    product: another precision, and compute-bound at M = 128."""
    if interpret:
        return None
    prec = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if prec in (None, "default", "bfloat16",
                                    "fastest") else None


def _mxu_dot(x, w, mxu):
    if mxu is not None:
        x, w = x.astype(mxu), w.astype(mxu)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _as_stack(w: jax.Array, layer) -> tuple:
    """``(w [L, K_pad, N_pad], layer [1] int32)``: the stack and the index
    of the layer the kernel reads; a 2-D pack is a stack of one."""
    if w.ndim == 2:
        assert layer is None, "a layer index needs a stacked pack"
        return w[None], jnp.zeros((1,), jnp.int32)
    assert w.ndim == 3 and layer is not None, (
        f"stacked pack {w.shape} needs the layer to read")
    return w, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


# ------------------------------------------------------------- epilogue
_EPI_ACTS = ("silu", "gelu", "tanh")


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """Statically-planned epilogue applied on the fp32 accumulator at the
    kernel's single store step (see module docstring).

    Application order: bias add -> glu combine (``act(gate) * up`` over
    the two column halves) OR plain activation -> tanh softcap ->
    residual add -> one cast + store.  ``bias`` / ``residual`` are flags;
    the operands themselves ride the call (``execute(..., bias=,
    residual=)``).  All ops run in fp32 — for fp32 operands the fused
    result is bit-identical to the unfused ``kernel -> XLA op`` sequence
    (the gate ``gemm.validate_plan`` runs per spec).
    """
    bias: bool = False
    act: str | None = None          # "silu" | "gelu" | "tanh"
    softcap: float | None = None    # cap * tanh(x / cap)
    residual: bool = False
    glu: str | None = None          # activation of the gate half

    def __post_init__(self):
        for name in (self.act, self.glu):
            if name is not None and name not in _EPI_ACTS:
                raise ValueError(f"unknown epilogue activation {name!r}; "
                                 f"choose from {_EPI_ACTS}")
        if self.act is not None and self.glu is not None:
            raise ValueError("act and glu are mutually exclusive (glu "
                             "already applies its activation to the gate)")

    @property
    def is_noop(self) -> bool:
        return not (self.bias or self.act or self.softcap is not None
                    or self.residual or self.glu)


_GELU_C = 0.7978845608028654        # sqrt(2 / pi)


def _gelu_tanh(x):
    # jax.nn.gelu's internals get rewritten differently inside the Pallas
    # interpreter vs plain XLA (bit drift ~5e-7); this explicit tanh
    # formulation lowers identically in both, which the fused-vs-unfused
    # bitwise contract needs.  Every epilogue-capable path (kernel, xla
    # backend, unfused layers) routes gelu through here.
    return 0.5 * x * (1.0 + jnp.tanh(_GELU_C * (x + 0.044715 * (x * x * x))))


def act_fn(name: str):
    """The repo-wide activation table (see ``_gelu_tanh`` for why gelu is
    hand-rolled).  Shared by the kernel epilogue, the XLA epilogue path,
    and the unfused ``models.layers`` ops, so fused == unfused holds
    bitwise for fp32.  Unknown names raise — a typo'd ``cfg.act`` must
    not silently compute tanh."""
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return _gelu_tanh
    if name == "tanh":
        return jnp.tanh
    raise ValueError(f"unknown activation {name!r}; choose from "
                     f"{_EPI_ACTS}")


_act_fn = act_fn


def _finish(spec: EpilogueSpec, acc, residual):
    """Post-activation epilogue steps, shared by kernel and reference.

    Softcap multiplies by the host-computed reciprocal instead of
    dividing: XLA rewrites ``x / const`` to ``x * (1/const)`` outside
    Pallas but not inside the interpreter, which would break the
    fused-vs-unfused bitwise contract."""
    if spec.softcap is not None:
        acc = spec.softcap * jnp.tanh(acc * (1.0 / spec.softcap))
    if spec.residual:
        acc = acc + residual
    return acc


def apply_epilogue_glu(g: jax.Array, u: jax.Array, spec: EpilogueSpec, *,
                       bias_g=None, bias_u=None, residual=None):
    """The glu epilogue on pre-split gate/up fp32 accumulators — the ONE
    definition shared by the kernel store step (two accumulator tiles)
    and the xla backend (two half dots), so both are bit-identical to
    the full-width reference."""
    g = g.astype(jnp.float32)
    u = u.astype(jnp.float32)
    if spec.bias:
        g = g + bias_g.astype(jnp.float32)
        u = u + bias_u.astype(jnp.float32)
    acc = _act_fn(spec.glu)(g) * u
    res = residual.astype(jnp.float32) if spec.residual else None
    return _finish(spec, acc, res)


def apply_epilogue(acc: jax.Array, spec: EpilogueSpec, *, bias=None,
                   residual=None) -> jax.Array:
    """Reference epilogue on a full fp32 accumulator array.

    This is THE semantics the fused kernel store step must match bitwise:
    the same jnp ops, in the same order, in fp32.  Backends that cannot
    fuse (xla) call this on the dot's fp32 result; the bit-exactness gate
    compares the fused kernel against ``unfused kernel -> this``.  For a
    ``glu`` spec ``acc`` is full width and the halves are combined here.
    """
    acc = acc.astype(jnp.float32)
    if spec.glu is not None:
        half = acc.shape[-1] // 2
        b = bias.astype(jnp.float32) if spec.bias else None
        return apply_epilogue_glu(
            acc[..., :half], acc[..., half:], spec,
            bias_g=b[..., :half] if spec.bias else None,
            bias_u=b[..., half:] if spec.bias else None,
            residual=residual)
    if spec.bias:
        acc = acc + bias.astype(jnp.float32)
    if spec.act is not None:
        acc = _act_fn(spec.act)(acc)
    res = None
    if spec.residual:
        res = residual.astype(jnp.float32)
    return _finish(spec, acc, res)


def splitk_combine(parts) -> jax.Array:
    """Deterministic fixed-order pairwise tree sum of split-K partials.

    ``parts``: a list of fp32 ``[M, N]`` partials (or a stacked
    ``[split_k, M, N]`` array), one per K slice, in slice order.  The
    combine order is a STATIC pairwise tree — (p0+p1)+(p2+p3)... — so
    the result is a pure function of the partial values, independent of
    which backend produced them: the Pallas kernel, the interpreter,
    the xla slice-dot run and the ``ref.gemm_splitk`` oracle all route
    through this one definition, which is what makes split-K results
    bitwise-reproducible per backend and kernel == oracle bitwise.
    """
    if not isinstance(parts, (list, tuple)):
        parts = [parts[i] for i in range(parts.shape[0])]
    parts = list(parts)
    assert parts, "splitk_combine needs at least one partial"
    while len(parts) > 1:
        parts = [parts[i] + parts[i + 1] if i + 1 < len(parts)
                 else parts[i] for i in range(0, len(parts), 2)]
    return parts[0]


def vmem_bytes(block_m: int, block_n: int, block_k: int,
               in_dtype=jnp.float32, *,
               epilogue: EpilogueSpec | None = None,
               weight_format: str = "fp32",
               split_k: int = 1,
               sparse_groups: int = 0,
               sparse_panels: int = 0) -> int:
    """Static VMEM footprint model for one grid step (double-buffered ins).

    A ``glu`` epilogue streams two weight tiles and carries two fp32
    accumulators.  The bias/residual operand tiles are budgeted
    UNCONDITIONALLY: a weight is packed once but may execute under
    different epilogues (w_down runs with and without the fused residual
    add), so the footprint a pack's blocks are clamped against must be
    the worst execute-time footprint — otherwise plan-time clamping
    could shrink below the pack's blocks and every execute would raise
    PlanMismatchError.

    ``weight_format`` sizes the STREAMED weight tile: a quantized pack
    streams int8 codes (1 B/elem) or 2-bit ternary bytes (0.25 B/elem)
    plus a per-column fp32 scale row, so quantized plans fit deeper /
    wider blocks in the same budget (repro.quant).

    ``split_k > 1`` budgets the decode lane's per-slice fp32 partials
    slab (``[split_k, block_m, block_n]``): the combine epilogue reads
    every slice's partial for one output tile, so the whole slab must
    be resident alongside the streaming tiles.

    ``sparse_groups > 0`` budgets the compressed-ternary walk instead
    of the dense K stream: the grid's K axis is the occupied-group list,
    so one step streams a ``(block_m, GROUP_K)`` x tile, a packed
    ``(GROUP_K/4, block_n)`` code tile and a single fp32 scale row —
    ``block_k`` is ignored — plus the scalar-prefetched group-offset
    index (int32 per occupied slot) and the ``sparse_panels``-wide
    occupancy matrix, resident once (not double-buffered).
    """
    isz = jnp.dtype(in_dtype).itemsize
    if weight_format == "fp32":
        x = block_m * block_k * isz
        w = block_k * block_n * isz
        scales = 0
    else:
        from repro.quant.formats import GROUP_K, weight_itemsize
        bk_eff = GROUP_K if sparse_groups > 0 else block_k
        x = block_m * bk_eff * isz
        w = int(bk_eff * block_n * weight_itemsize(weight_format))
        # per-(column, K-group) fp32 scale slab for this tile
        scales = max(1, bk_eff // GROUP_K) * block_n * 4
    acc = block_m * block_n * 4          # fp32 accumulator scratch
    out = block_m * block_n * isz
    glu = epilogue is not None and epilogue.glu is not None
    if glu:
        w *= 2
        scales *= 2
        acc *= 2
    # worst-case epilogue operand headroom (fp32 bias row + residual tile)
    extra = block_n * 4 * (2 if glu else 1) + block_m * block_n * 4
    if split_k > 1:     # decode lane: per-slice fp32 partials slab
        extra += split_k * block_m * block_n * 4
    if sparse_groups > 0:   # sparse walk: group index + occupancy matrix
        extra += 4 * sparse_groups * (1 + max(1, sparse_panels))
    return 2 * (x + w + scales) + acc + out + extra   # 2x: double buffering


def _gemm_kernel(layer_ref, x_ref, w_ref, *refs, nk: int,
                 spec: EpilogueSpec | None = None, mxu=None):
    """One (i, j, k) grid step: acc[i,j] += x[i,k] @ w[k,j].

    The Z-discipline of the paper, verbatim in Pallas terms: the accumulator
    is zeroed only at k == 0 (skip-Z analogue) and the output is stored only
    at the last K step (STZ).  Without the @pl.when guards, one (i, j)
    tile's partial sums leak into the next — the exact silent-drift bug the
    paper calls correctness-critical.

    ``layer_ref`` (the prefetched layer index) is read by the weight's
    index map only.  ``refs`` trail the optional epilogue operands:
    [bias], [residual], then o_ref and the accumulator scratch.  The
    epilogue runs INSIDE the STZ step, on the fp32 accumulator, before
    the single cast+store.
    """
    del layer_ref
    refs = list(refs)
    acc_ref = refs.pop()
    o_ref = refs.pop()
    bias_ref = refs.pop(0) if spec is not None and spec.bias else None
    res_ref = refs.pop(0) if spec is not None and spec.residual else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mxu_dot(x_ref[...], w_ref[...], mxu)

    @pl.when(k == nk - 1)
    def _store():
        acc = acc_ref[...]
        if spec is not None:
            if spec.bias:
                acc = acc + bias_ref[...]          # [1, bn] broadcasts
            if spec.act is not None:
                acc = _act_fn(spec.act)(acc)
            acc = _finish(spec, acc, res_ref[...] if res_ref is not None
                          else None)
        o_ref[...] = acc.astype(o_ref.dtype)


def _gemm_glu_kernel(layer_ref, x_ref, wg_ref, wu_ref, *refs, nk: int,
                     spec: EpilogueSpec, mxu=None):
    """GLU variant: TWO accumulators ride the K grid — the gate and up
    column panels of one horizontally fused weight (``core.packing
    .pack_fused``).  x is loaded once per step and feeds both dots; the
    store step combines ``act(gate) * up`` on the fp32 accumulators, so
    the [M, 2F] intermediate never reaches HBM."""
    del layer_ref
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
    acc_g_ref[...] += _mxu_dot(x, wg_ref[...], mxu)
    acc_u_ref[...] += _mxu_dot(x, wu_ref[...], mxu)

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
    static_argnames=("block_m", "block_n", "block_k", "interpret",
                     "out_dtype", "epilogue"),
)
def panel_gemm(
    x: jax.Array,               # [M_pad, K_pad]  activations (pre-padded)
    w: jax.Array,               # [K_pad, N_pad] or stacked [L, K_pad, N_pad]
    bias: jax.Array | None = None,       # [N_pad] fp32 (iff epilogue.bias)
    residual: jax.Array | None = None,   # [M_pad, N_out_pad] fp32
    layer: jax.Array | None = None,      # int32 scalar (iff w is stacked)
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=None,
    epilogue: EpilogueSpec | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C[M_pad, N_pad] = epilogue(x @ w[layer]) via MXU panel tiles.

    Shapes must be pre-padded to block multiples (the pack does this once at
    load for w; ops.py pads x per call — cheap, M=128 at prefill).  With a
    ``glu`` epilogue ``w`` holds [gate | up] column halves (each half
    block-aligned) and the output is [M_pad, N_pad // 2].  A stacked ``w``
    is read in place at ``layer`` (module docstring).
    """
    w, layer = _as_stack(w, layer)
    m, k = x.shape
    _, k2, n = w.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{n},{k}) not aligned to blocks "
        f"({block_m},{block_n},{block_k}); pack first")
    nk = k // block_k
    out_dtype = out_dtype or x.dtype
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    glu = spec is not None and spec.glu is not None
    n_out = n // 2 if glu else n
    if glu:
        assert n % 2 == 0 and n_out % block_n == 0, (
            f"glu epilogue needs block-aligned column halves; got N={n} "
            f"with block_n={block_n} — pack with pack_fused")
    assert (bias is not None) == bool(spec is not None and spec.bias)
    assert (residual is not None) == bool(spec is not None and spec.residual)

    # index maps take (i, j, kk, layer_ref); the weight's reads the layer
    w_block = (pl.Squeezed(), block_k, block_n)
    ops = [x, w]
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk, li: (i, kk)),
        pl.BlockSpec(w_block, lambda i, j, kk, li: (li[0], kk, j)),
    ]
    half_tiles = n_out // block_n
    if glu:        # up panel: same array, column-offset index map
        ops.append(w)
        in_specs.append(pl.BlockSpec(
            w_block, lambda i, j, kk, li: (li[0], kk, j + half_tiles)))
    if spec is not None and spec.bias:
        b2 = bias.reshape(1, n).astype(jnp.float32)
        ops.append(b2)
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, kk, li: (0, j)))
        if glu:
            ops.append(b2)
            in_specs.append(pl.BlockSpec(
                (1, block_n), lambda i, j, kk, li: (0, j + half_tiles)))
    if spec is not None and spec.residual:
        assert residual.shape == (m, n_out), (
            f"residual {residual.shape} vs output ({m},{n_out})")
        ops.append(residual.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, j, kk, li: (i, j)))

    mxu = mxu_operand_dtype(interpret)
    if glu:
        kernel = functools.partial(_gemm_glu_kernel, nk=nk, spec=spec,
                                   mxu=mxu)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((block_m, block_n), jnp.float32)]
    else:
        kernel = functools.partial(_gemm_kernel, nk=nk, spec=spec, mxu=mxu)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    return pl.pallas_call(
        kernel,
        name="panel_gemm_glu" if glu else "panel_gemm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // block_m, n_out // block_n, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, kk, li: (i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(layer, *ops)


# ----------------------------------------------------------- split-K lane
def _splitk_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, nks: int,
                   mxu=None):
    """One (s, i, j, kk) grid step of the split-K partials pass:
    acc[s,i,j] += x[i, s*nks + kk] @ w[s*nks + kk, j].

    The Z-discipline is per SLICE: the accumulator zeroes at the first
    K block of the slice and the slice's fp32 partial is stored (never
    cast) at its last — the combine tree runs outside, shared with
    every backend.  ``s`` is a PARALLEL grid dimension: at decode
    (M <= 8, one row panel) the (i, j) grid exposes almost no parallel
    output panels, and ``s`` restores occupancy on the reduction side —
    the paper's fine-panel lever, generalized to K.
    """
    del layer_ref
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mxu_dot(x_ref[...], w_ref[...], mxu)

    @pl.when(kk == nks - 1)
    def _store():
        o_ref[...] = acc_ref[...][None]


@functools.partial(
    jax.jit,
    static_argnames=("split_k", "block_m", "block_n", "block_k",
                     "interpret", "out_dtype", "epilogue"),
)
def panel_gemm_splitk(
    x: jax.Array,               # [M_pad, K_pad]  activations (pre-padded)
    w: jax.Array,               # [K_pad, N_pad] or stacked [L, K_pad, N_pad]
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    layer: jax.Array | None = None,      # int32 scalar (iff w is stacked)
    *,
    split_k: int,
    block_m: int = DECODE_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=None,
    epilogue: EpilogueSpec | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(splitk_combine(per-slice x @ w)) — the decode lane.

    Grid ``(s, i, j, kk)``: ``split_k`` K slices accumulate independent
    fp32 partials (all three leading dims parallel), combined by the
    deterministic :func:`splitk_combine` tree; the epilogue then runs
    on the combined fp32 accumulator via the shared
    :func:`apply_epilogue` (so fused == unfused stays bit-identical,
    glu included).  Bit-identical to ``ref.gemm_splitk`` at the same
    ``(block_k, split_k)`` — gated by ``gemm.validate_plan``.  A stacked
    ``w`` is read in place at ``layer``, as ``panel_gemm`` reads it.
    """
    w, layer = _as_stack(w, layer)
    m, k = x.shape
    _, k2, n = w.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert split_k >= 1 and k % split_k == 0, (
        f"K={k} not divisible by split_k={split_k}")
    ks = k // split_k
    assert m % block_m == 0 and n % block_n == 0 and ks % block_k == 0, (
        f"shapes ({m},{n},{k}) / slice depth {ks} not aligned to blocks "
        f"({block_m},{block_n},{block_k}); pack first")
    nks = ks // block_k
    out_dtype = out_dtype or x.dtype
    spec = epilogue
    if spec is not None and spec.is_noop:
        spec = None
    glu = spec is not None and spec.glu is not None
    n_out = n // 2 if glu else n
    if glu:
        assert n % 2 == 0 and n_out % block_n == 0, (
            f"glu epilogue needs block-aligned column halves; got N={n} "
            f"with block_n={block_n} — pack with pack_fused")
    assert (bias is not None) == bool(spec is not None and spec.bias)
    assert (residual is not None) == bool(spec is not None
                                          and spec.residual)

    partials = pl.pallas_call(
        functools.partial(_splitk_kernel, nks=nks,
                          mxu=mxu_operand_dtype(interpret)),
        name="panel_gemm_splitk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(split_k, m // block_m, n // block_n, nks),
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda s, i, j, kk, li: (i, s * nks + kk)),
                pl.BlockSpec((pl.Squeezed(), block_k, block_n),
                             lambda s, i, j, kk, li: (li[0], s * nks + kk,
                                                      j)),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda s, i, j, kk, li: (s, i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((split_k, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(layer, x, w)
    acc = splitk_combine(partials)
    if spec is not None:
        acc = apply_epilogue(acc, spec, bias=bias, residual=residual)
    return acc.astype(out_dtype)
