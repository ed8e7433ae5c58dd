"""Backend registry — one extension point instead of a process global.

The seed steered its three ad-hoc entry points with a module-level
``_DEFAULT_IMPL`` read from ``REPRO_GEMM_IMPL`` at import time; nothing
outside that module could add a backend or scope a choice to one engine.
Here backends are first-class registry entries:

  * ``xla``       — one shape-agnostic dot (the Accelerate-dispatch
                    analogue, and the default for most plans).  Ignores the
                    plan's blocking (``needs_blocks=False``), so execute()
                    skips the block padding for it.
  * ``pallas``    — the compiled panel kernel (TPU deployment path, and
                    the TPU's default for the layer scan's stacked
                    float32 packs, ``device_default``).
  * ``interpret`` — the same kernel through the Pallas interpreter:
                    kernel-validation mode, bit-identical to
                    ``kernels/ref.gemm_blocked`` by construction.

``register_backend`` is the hook extensions use (the quant subsystem's
dequant-fused runs ride the same registry as ``run_quant`` entries;
batched GEMM / remote offload are future extensions).  The
``REPRO_GEMM_IMPL`` env var is REMOVED along with the legacy
``core/panel_gemm`` shims — this surface takes ``backend=`` explicitly
or via ``use_backend(...)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from repro.kernels import panel_gemm as _kernel

# run(x_p, w_p, *, block_m, block_n, block_k, out_dtype) -> y
RunFn = Callable[..., jax.Array]


@dataclasses.dataclass(frozen=True)
class Backend:
    """``run`` executes fp-dtype operands.  ``run_quant`` (optional) is
    the dequant-fused entry for quantized packs —
    ``run_quant(x_p, codes, scales, *, weight_format, block_m, block_n,
    block_k, out_dtype, [epilogue kwargs])`` — dispatched by execute()
    only when the plan's ``weight_format`` is quantized.  A backend
    without it rejects quantized plans (registered extensions predating
    the quant subsystem keep working for fp32 plans unchanged).
    ``run_grouped`` (optional) runs grouped plans —
    ``run_grouped(x, bank [G, K_pad, N_pad], group_sizes [G], *,
    out_dtype, epilogue)`` over rows sorted by group, where any group may
    be empty: a grouped plan that reads a stacked bank in place
    (``GemmPlan.in_place``) hands it the whole stack folded to
    ``[L * G, K_pad, N_pad]``, with the sizes zero outside the layer's
    ``G``.  ``in_place``: ``run`` takes a stacked pack
    ``[L, K_pad, N_pad]`` and ``layer=`` and reads that layer's panels
    where they lie; execute() slices the layer out for a backend without
    it."""
    name: str
    run: RunFn
    needs_blocks: bool = True    # False: shape-agnostic, skip block padding
    description: str = ""
    run_quant: RunFn | None = None
    run_grouped: RunFn | None = None
    in_place: bool = False


_REGISTRY: dict[str, Backend] = {}
_LOCK = threading.Lock()
_STATE = threading.local()       # per-thread default-backend override stack


class UnknownBackendError(KeyError):
    pass


def register_backend(name: str, run: RunFn, *, needs_blocks: bool = True,
                     description: str = "",
                     run_quant: RunFn | None = None,
                     run_grouped: RunFn | None = None,
                     in_place: bool = False,
                     overwrite: bool = False) -> Backend:
    """Register a GEMM backend under ``name`` (the extension hook)."""
    with _LOCK:
        if name in _REGISTRY and not overwrite:
            raise ValueError(f"backend {name!r} already registered; "
                             f"pass overwrite=True to replace it")
        b = Backend(name=name, run=run, needs_blocks=needs_blocks,
                    description=description, run_quant=run_quant,
                    run_grouped=run_grouped, in_place=in_place)
        _REGISTRY[name] = b
        return b


def unregister_backend(name: str) -> None:
    with _LOCK:
        if name in _BUILTIN:
            raise ValueError(f"cannot unregister builtin backend {name!r}")
        _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownBackendError(
            f"unknown GEMM backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def list_backends() -> list[str]:
    return sorted(_REGISTRY)


# ----------------------------------------------------------- default choice
def device_default(platform: str, *, weight_format: str = "fp32",
                   stacked: bool = False, m: int | None = None) -> str:
    """The backend a plan gets on ``platform`` when no scope names one.

    On a TPU the panel kernel runs a float32 plan for a stacked pack (a
    layer scan's weights, ``execute(..., layer=)``) whose rows fit one
    row panel (M <= ``DEFAULT_BLOCK_M``): it reads the layer's float32
    panels in place, once, and rounds them to bf16 in VMEM, where XLA's
    default-precision dot first writes a bf16 copy of the whole stack
    and then reads it; on a v5e it takes about half XLA's time at M = 128
    and M = 4 (PERF.md §6).  Everything else keeps ``xla``: a
    single 2-D weight (the LM head), which XLA's dot reads as float32
    with no copy and its wider tiles beat the kernel's; more rows than
    one panel (the kernel streams the weights once per 128-row panel);
    quantized plans (the int8 packs were measured on ``xla``'s
    dequantizing run); and every other device.  The choice is one
    device's: a step partitioned over a mesh of several devices names
    ``xla`` in its scope instead (``Engine(mesh=)``), since XLA cannot
    partition the kernel's custom call."""
    if (platform == "tpu" and weight_format == "fp32" and stacked
            and m is not None and m <= _kernel.DEFAULT_BLOCK_M):
        return "pallas"
    return "xla"


def device_grouped_in_place(platform: str, *, dtype: str = "float32",
                            stacked: bool = False) -> bool:
    """Whether a grouped plan for a stacked bank ``[L, G, K_pad, N_pad]``
    reads it in place on ``platform`` when no scope names a backend.

    On a TPU a float32 stacked bank folds its layer axis into the group
    axis: the grouped run gets the whole stack as ``[L * G, K_pad,
    N_pad]`` (a bitcast) with group sizes that are zero outside the
    layer's ``G``, and the TPU's grouped-matmul kernel visits only the
    live groups' tiles.  XLA cannot fuse a layer's dynamic slice into
    that kernel, so the sliced run copies the layer's bank first
    (277 MB a DeepSeek-V2-Lite MoE layer, PERF.md §5).  Every other
    platform keeps the slice: XLA:CPU's ``ragged_dot`` computes every
    group, the empty ones too.  The choice is one device's, as
    :func:`device_default`'s is: a step partitioned over a mesh names a
    backend in its scope (``Engine(mesh=)``), and folding the layer
    into the bank's expert axis, which the mesh partitions, would move
    the bank between devices."""
    return platform == "tpu" and dtype == "float32" and stacked


def default_grouped_in_place(**plan_kw) -> bool:
    """:func:`device_grouped_in_place` of ``jax.default_backend()`` —
    False inside a ``use_backend`` scope, which pins the run it names."""
    if getattr(_STATE, "stack", None):
        return False
    return device_grouped_in_place(jax.default_backend(), **plan_kw)


def default_backend(**plan_kw) -> str:
    """The backend a plan gets when none is requested: the innermost
    ``use_backend`` scope's, else the device's (:func:`device_default`
    of ``jax.default_backend()`` and the plan's ``weight_format``,
    ``stacked`` and ``m``)."""
    stack = getattr(_STATE, "stack", None)
    if stack:
        return stack[-1]
    return device_default(jax.default_backend(), **plan_kw)


def resolve_backend(name: str | None, **plan_kw) -> str:
    name = name or default_backend(**plan_kw)
    get_backend(name)            # validate early, at plan time
    return name


@contextlib.contextmanager
def use_backend(name: str | None) -> Iterator[None]:
    """Scope the default backend (e.g. one Engine tracing its steps).
    ``None`` is a no-op scope, so call sites can thread an optional."""
    if name is None:
        yield
        return
    get_backend(name)
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


# ------------------------------------------------------------ builtin runs
# Epilogue kwargs (epilogue=, bias=, residual=) are passed ONLY when the
# plan carries an EpilogueSpec, and split_k= only when the plan's
# split_k > 1, so registered backends that predate either surface keep
# working for plain plans unchanged.
def _xla_splitk_acc(x_p, w_p, split_k):
    """Slice dots + the shared fixed-order combine tree: the xla form of
    the decode lane's split-K accumulation.  Deterministic per backend;
    within-slice accumulation is XLA's dot (allclose, not bitwise, to
    the kernel's blocked partials — the standing xla-vs-kernel
    contract), while the combine order is the shared tree, so the
    result is a pure function of the slice-dot values."""
    k = x_p.shape[-1]
    ks = k // split_k
    parts = [jnp.dot(x_p[:, s * ks:(s + 1) * ks],
                     w_p[s * ks:(s + 1) * ks, :],
                     preferred_element_type=jnp.float32)
             for s in range(split_k)]
    return _kernel.splitk_combine(parts)


def _run_xla(x_p, w_p, *, block_m, block_n, block_k, out_dtype,
             epilogue=None, bias=None, residual=None, split_k=1):
    del block_m, block_n, block_k
    if split_k > 1:
        acc = _xla_splitk_acc(x_p, w_p, split_k)
    else:
        acc = jnp.dot(x_p, w_p, preferred_element_type=jnp.float32)
    if epilogue is not None:
        # same jnp ops as the kernel store phase, on the fp32 result —
        # the "fusion" here is XLA's own elementwise fusion, but the
        # numerics contract (fp32 epilogue, single final cast) is
        # identical to the Pallas path's
        acc = _kernel.apply_epilogue(acc, epilogue, bias=bias,
                                     residual=residual)
    return acc.astype(out_dtype or x_p.dtype)


def _run_pallas(x_p, w_p, *, block_m, block_n, block_k, out_dtype,
                epilogue=None, bias=None, residual=None, split_k=1,
                layer=None, interpret=False):
    if split_k > 1:
        return _kernel.panel_gemm_splitk(
            x_p, w_p, bias, residual, layer, split_k=split_k,
            block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, epilogue=epilogue, interpret=interpret)
    return _kernel.panel_gemm(x_p, w_p, bias, residual, layer,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k, out_dtype=out_dtype,
                              epilogue=epilogue, interpret=interpret)


def _run_interpret(x_p, w_p, **kw):
    return _run_pallas(x_p, w_p, interpret=True, **kw)


# Dequant-fused runs (repro.quant): same trio, streaming codes + scales.
# The xla run dequantizes inside ONE jitted computation, so XLA fuses
# the cast/scale into the dot's operand path — a dequant-THEN-sgemm
# baseline instead materializes the fp32 weight as a separate dispatch,
# which is exactly the round-trip the fused path deletes.
def _run_quant_xla(x_p, codes, scales, *, weight_format, block_m, block_n,
                   block_k, out_dtype, epilogue=None, bias=None,
                   residual=None, split_k=1, sparse_layout=None):
    del block_m, block_n, block_k
    from repro.quant import formats as _F
    if sparse_layout is not None:
        # compressed-ternary lane: gather the activation columns of the
        # surviving K-groups (static slices — the group union is pack
        # metadata) and dot against the compacted dequantized panels.
        # The dense lane materializes the FULL K x N fp32 dequant; this
        # one materializes only the occupied fraction — the weight-byte
        # (and dequant-flop) cut IS the sparse win on this backend.
        assert split_k == 1, "sparse plans run split_k=1 (policy-forced)"
        from repro.quant.formats import GROUP_K
        k_groups, group_index, _bitmap, _bn = sparse_layout
        if not group_index:          # fully-zero weight
            acc = jnp.zeros((x_p.shape[0], codes.shape[-1]), jnp.float32)
        else:
            if len(group_index) == k_groups:
                x_c = x_p            # degenerate union: nothing removed
            else:
                x_c = jnp.concatenate(
                    [x_p[:, g * GROUP_K:(g + 1) * GROUP_K]
                     for g in group_index], axis=1)
            w = _F.dequantize_padded(codes, scales, weight_format)
            w = jax.lax.optimization_barrier(w)
            acc = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
        if epilogue is not None:
            acc = _kernel.apply_epilogue(acc, epilogue, bias=bias,
                                         residual=residual)
        return acc.astype(out_dtype or x_p.dtype)
    if split_k > 1:
        # per-slice dequant + slice dots: each K slice's dequantized
        # panel is materialized (barriered, same rationale as below) and
        # consumed immediately, then the shared combine tree sums the
        # fp32 partials in fixed order
        kdiv = 4 if weight_format == "ternary" else 1
        from repro.quant.formats import GROUP_K
        k = x_p.shape[-1]
        ks = k // split_k
        parts = []
        for s in range(split_k):
            w_s = _F.dequantize_padded(
                codes[s * ks // kdiv:(s + 1) * ks // kdiv],
                scales[s * ks // GROUP_K:(s + 1) * ks // GROUP_K],
                weight_format)
            w_s = jax.lax.optimization_barrier(w_s)
            parts.append(jnp.dot(x_p[:, s * ks:(s + 1) * ks], w_s,
                                 preferred_element_type=jnp.float32))
        acc = _kernel.splitk_combine(parts)
    else:
        w = _F.dequantize_padded(codes, scales, weight_format)
        # keep the dequantized panels a materialized dot operand: letting
        # XLA:CPU fuse the convert/scale INTO the dot knocks it off the
        # fast library-dot path (measured 20-30% slower at wide N); the
        # barrier costs nothing numerically (values are identical bitwise)
        w = jax.lax.optimization_barrier(w)
        acc = jnp.dot(x_p, w, preferred_element_type=jnp.float32)
    if epilogue is not None:
        acc = _kernel.apply_epilogue(acc, epilogue, bias=bias,
                                     residual=residual)
    return acc.astype(out_dtype or x_p.dtype)


def _run_quant_pallas(x_p, codes, scales, *, weight_format, block_m,
                      block_n, block_k, out_dtype, epilogue=None,
                      bias=None, residual=None, split_k=1,
                      interpret=False, sparse_layout=None):
    from repro.quant import kernels as _qk
    if sparse_layout is not None:
        assert split_k == 1, "sparse plans run split_k=1 (policy-forced)"
        return _qk.sparse_quant_panel_gemm(
            x_p, codes, scales, bias, residual,
            sparse_layout=sparse_layout, block_m=block_m,
            block_n=block_n, out_dtype=out_dtype, epilogue=epilogue,
            interpret=interpret)
    if split_k > 1:
        return _qk.quant_panel_gemm_splitk(
            x_p, codes, scales, bias, residual,
            weight_format=weight_format, split_k=split_k,
            block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, epilogue=epilogue, interpret=interpret)
    return _qk.quant_panel_gemm(x_p, codes, scales, bias, residual,
                                weight_format=weight_format,
                                block_m=block_m, block_n=block_n,
                                block_k=block_k, out_dtype=out_dtype,
                                epilogue=epilogue, interpret=interpret)


def _run_quant_interpret(x_p, codes, scales, **kw):
    return _run_quant_pallas(x_p, codes, scales, interpret=True, **kw)


# Grouped run: the held experts of a MoE layer as one grouped GEMM.
def _run_grouped_xla(x, bank, group_sizes, *, out_dtype, epilogue=None):
    """XLA's ragged dot: rows sorted by group, ``group_sizes[g]`` of them
    through ``bank[g]``, fp32 accumulate at the default precision (the
    activations promote to the bank's dtype, as ``jnp.dot`` promotes
    them in ``_run_xla``); rows past the groups' sum come back zero.
    ``ragged_dot`` leaves those rows unspecified (the TPU's kernel need
    not write them), so they are selected away here, in the fusion of
    the epilogue (the experts' glu), which runs on the fp32 result."""
    group_sizes = group_sizes.astype(jnp.int32)
    acc = jax.lax.ragged_dot(x.astype(bank.dtype), bank, group_sizes,
                             preferred_element_type=jnp.float32)
    live = jnp.arange(acc.shape[0])[:, None] < jnp.sum(group_sizes)
    acc = jnp.where(live, acc, 0.0)
    if epilogue is not None:
        acc = _kernel.apply_epilogue(acc, epilogue)
    return acc.astype(out_dtype or x.dtype)


register_backend("xla", _run_xla, needs_blocks=False,
                 description="shape-agnostic XLA dot (Accelerate analogue)",
                 run_quant=_run_quant_xla, run_grouped=_run_grouped_xla)
register_backend("pallas", _run_pallas,
                 description="compiled Pallas panel kernel (TPU deploy)",
                 run_quant=_run_quant_pallas, in_place=True)
register_backend("interpret", _run_interpret,
                 description="Pallas interpreter (kernel validation)",
                 run_quant=_run_quant_interpret, in_place=True)
_BUILTIN = frozenset(_REGISTRY)
