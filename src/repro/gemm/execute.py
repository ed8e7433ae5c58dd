"""``execute(plan, x, w)`` — run a planned GEMM; all per-call work that a
plan can remove has been removed at plan/pack time.

The weight operand may be:

  * a ``PackedWeight`` (paid once at model load — the plan's ``prepack``
    lever): per call only M-padding of the activations remains.  A
    stacked pack (every array leading ``[L, ...]``, the layer scan's
    weights) runs with ``layer=``: a float32 plan on a backend that
    reads in place (``pallas``, ``interpret``) reads that layer's panels
    where they lie; a grouped plan that reads in place (``p.in_place``,
    a float32 bank on a single TPU) hands the grouped run the whole
    stack with the layer folded into the group axis; for every other
    run (``xla``, quantized packs, grouped banks elsewhere) execute
    indexes the layer out of each array first;
  * a ``QuantizedPackedWeight`` (repro.quant — quantized AND packed at
    load): the plan carries its ``weight_format`` and the backend's
    dequant-fused ``run_quant`` entry streams codes + scales;
  * a raw array (``[K, N]``, or ``[N, K]`` when the plan was built with
    ``transposed=True``): the transpose+pad runs inside the call — the
    honest cblas/BNNSMatMul baseline of the paper.

Numerics contract (the paper's discipline): for a given block triple the
result is bit-identical across packed / per-call operands and across the
``pallas`` / ``interpret`` backends, and bit-identical to
``kernels/ref.gemm_blocked`` at the plan's ``block_k`` — asserted by
``tests/test_gemm_api.py`` and gateable at plan time via
``plan(..., validate=True)``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.gemm import backends as _backends
from repro.gemm.plan import GemmPlan, PACK_NONE
from repro.gemm.policy import _bitexact_gate
from repro.kernels.panel_gemm import EpilogueSpec  # noqa: F401 (re-export)
from repro.obs import recorder as _flight
from repro.quant.formats import (QuantizedPackedWeight,
                                 SparseTernaryPackedWeight)


class PlanMismatchError(ValueError):
    pass


def lead_m(x: jax.Array) -> int:
    """Row count of ``x[..., K]`` flattened to 2-D — the M a plan for
    this operand must carry."""
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return m


def _check(cond: bool, msg: str):
    if not cond:
        raise PlanMismatchError(msg)


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _pad_cols(x: jax.Array, to: int) -> jax.Array:
    pad = to - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def execute(p: GemmPlan, x: jax.Array, w, *, bias=None, residual=None,
            out_dtype=None, group_sizes=None, layer=None) -> jax.Array:
    """y[..., N_out] = epilogue(x[..., K] @ w), dispatched per ``p``.

    Observability (repro.obs): when a flight recorder or manifest scope
    is active, the dispatch is recorded — eager calls into the
    recorder's ring (wall-timed; fenced with ``block_until_ready`` when
    the recorder opted in, since async dispatch otherwise times the
    enqueue), traced calls (operands are jit tracers — every serving
    step) into the trace-time manifest of the enclosing
    ``obs.manifest_scope``.  The inactive path is one module-level int
    check; the active-path branch below never touches the math.

    Shapes and pack blocks are checked against the plan; ``p.dtype`` is
    cache-keying metadata, NOT an executed constraint — mixed-dtype
    operands (bf16 activations against fp32-packed weights in the
    dry-run, and vice versa) are legitimate and promote as jnp.dot
    would.  The bit-exactness gate (``validate_plan``) attests the
    block-order accumulation discipline, which holds per operand dtype.

    Epilogue operands: ``bias`` [N] and ``residual`` [..., N_out] must be
    supplied exactly when the plan's ``EpilogueSpec`` declares them; both
    are cast to fp32 here (the epilogue contract runs on the fp32
    accumulator).  A plan with ``fused_n_splits`` returns the full
    concatenated output — slice per part with :func:`split_fused` — except
    under a glu epilogue, where the halves are combined in the store step
    and only the single ``p.n_out``-wide result comes back.

    A grouped plan (``p.groups``) runs a grouped pack over rows sorted by
    group: ``group_sizes`` [groups] int32 says how many of the leading
    rows each group's weight takes, in order; rows past their sum come
    back as zeros.

    ``layer`` (an int32 scalar, traced in the layer scan) selects the
    layer of a stacked pack ``w`` (module docstring).
    """
    stacked = layer is not None
    if stacked and not _reads_in_place(p):
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, layer, 0, keepdims=False), w)
        layer = None
    if p.groups:
        run = _execute_grouped
        kw = {"group_sizes": group_sizes, "layer": layer}
    else:
        run = _execute_impl
        kw = {"bias": bias, "residual": residual, "layer": layer}
    if not _flight._HOT:
        return run(p, x, w, out_dtype=out_dtype, **kw)
    rec = _flight.active_recorder()
    t0 = time.perf_counter()
    y = run(p, x, w, out_dtype=out_dtype, **kw)
    if isinstance(y, jax.core.Tracer):
        # jit-trace time: no wall clock exists per dispatch — register
        # the plan into the open manifest scope instead (once per
        # compilation; obs.report apportions tick time at export)
        _flight.on_traced(p, lead_m(x), stacked=stacked,
                          in_place=layer is not None)
    elif rec is not None:
        fenced = rec.fence
        if fenced:
            jax.block_until_ready(y)
        rec.record(p, lead_m(x), wall_s=time.perf_counter() - t0,
                   fenced=fenced)
    return y


def _reads_in_place(p: GemmPlan) -> bool:
    """Whether ``p``'s run reads one layer of a stacked pack where it
    lies (``execute(..., layer=)``): a grouped plan resolved
    ``in_place``, or a float32, ungrouped plan on a backend registered
    ``in_place``."""
    if p.groups:
        return p.in_place
    return not p.quantized and _backends.get_backend(p.backend).in_place


def _execute_impl(p: GemmPlan, x: jax.Array, w, *, bias=None,
                  residual=None, out_dtype=None, layer=None) -> jax.Array:
    backend = _backends.get_backend(p.backend)
    spec = p.epilogue
    _check((bias is not None) == bool(spec is not None and spec.bias),
           f"bias operand vs plan epilogue {spec} ({p.describe()})")
    _check((residual is not None) == bool(spec is not None
                                          and spec.residual),
           f"residual operand vs plan epilogue {spec} ({p.describe()})")
    lead = x.shape[:-1]
    _check(x.shape[-1] == p.k,
           f"operand K={x.shape[-1]} vs plan K={p.k} ({p.describe()})")
    x2 = x.reshape(-1, p.k)
    m = x2.shape[0]
    _check(m == p.m, f"operand M={m} vs plan M={p.m}; plans are "
                     f"shape-resolved — re-plan for this batch")

    quant = isinstance(w, QuantizedPackedWeight)
    _check(quant == p.quantized,
           f"operand {'is' if quant else 'is not'} a quantized pack but "
           f"plan weight_format={p.weight_format!r} ({p.describe()}); "
           f"re-plan via plan_for_packed")
    if quant:
        _check(w.fmt == p.weight_format,
               f"pack format {w.fmt!r} vs plan "
               f"weight_format={p.weight_format!r}")
        sparse = isinstance(w, SparseTernaryPackedWeight)
        _check(sparse == p.sparse,
               f"operand {'is' if sparse else 'is not'} a sparse-ternary "
               f"pack but plan density_bucket={p.density_bucket} "
               f"({p.describe()}); re-plan via plan_for_packed")
        if sparse:
            _check(w.density_bucket == p.density_bucket,
                   f"pack density_bucket={w.density_bucket} vs plan "
                   f"density_bucket={p.density_bucket}; the pack was "
                   f"re-quantized since the plan resolved — re-plan")
    _check(layer is None or (isinstance(w, packing.PackedWeight)
                             and w.data.ndim == 3),
           f"layer= reads a stacked pack ({p.describe()})")
    if isinstance(w, packing.PackedWeight):
        _check((w.k, w.n) == (p.k, p.n),
               f"packed weight {w.shape} vs plan ({p.k},{p.n})")
        _check((w.block_n, w.block_k) == (p.block_n, p.block_k),
               f"pack blocks ({w.block_n},{w.block_k}) vs plan "
               f"({p.block_n},{p.block_k}); pack with pack_for_plan()")
        _check(w.n_splits == p.fused_n_splits,
               f"pack splits {w.n_splits} vs plan {p.fused_n_splits}")
        w_p = w.data
    else:
        _check(not p.fused_n_splits and not p.glu,
               "fused plans execute against pack_fused weights only "
               "(a raw concat cannot keep the parts block-aligned)")
        ww = w.T if p.transposed else w
        _check(ww.shape == (p.k, p.n),
               f"weight {tuple(ww.shape)} vs plan ({p.k},{p.n})")
        # The pack decision is the PLAN's, not the backend's: the percall
        # baseline pays its transpose+pad even when the compute loop runs
        # through the shape-agnostic xla dot.
        # PACK_NONE (the raw-dot analogue) skips it — unless the backend
        # is a panel kernel that physically needs the blocked layout.
        if p.pack != PACK_NONE or backend.needs_blocks:
            w_p = packing.pack_percall(ww, transposed=False,
                                       block_n=p.block_n,
                                       block_k=p.block_k)
        else:
            w_p = ww

    # padded geometry: a ternary pack stores four K rows per codes row,
    # so the codes' leading dim is NOT the padded contraction depth
    k_pad = w.k_pad if quant else w_p.shape[-2]
    n_pad = w_p.shape[-1]
    if k_pad != p.k:                 # weight K was pack-padded: pad x too
        x2 = _pad_cols(x2, k_pad)
    if backend.needs_blocks:
        x2 = _pad_rows(x2, p.block_m)

    out_cols = n_pad // 2 if p.glu else n_pad
    epi_kw = {}
    if p.split_k > 1:
        # decode lane: the K slices must be whole (and, for kernel
        # backends, whole-block) — the policy guarantees this for plans
        # it resolved; explicit split_k overrides are checked here
        _check(k_pad % p.split_k == 0
               and (k_pad // p.split_k) % p.block_k == 0,
               f"split_k={p.split_k} does not cut padded K={k_pad} into "
               f"whole block_k={p.block_k} slices ({p.describe()})")
        epi_kw["split_k"] = p.split_k
    if layer is not None:
        epi_kw["layer"] = layer
    if spec is not None:
        b2 = r2 = None
        if bias is not None:
            if p.fused_n_splits:
                # per-part biases, padded into the pack's column layout
                parts = (list(bias) if isinstance(bias, (tuple, list))
                         else None)
                _check(parts is not None
                       and len(parts) == len(p.fused_n_splits),
                       f"fused plan needs one bias per part "
                       f"{p.fused_n_splits}")
                padded = []
                for b, ni in zip(parts, p.fused_n_splits):
                    b = jnp.asarray(b, jnp.float32).reshape(-1)
                    _check(b.shape[0] == ni,
                           f"bias width {b.shape[0]} vs part width {ni}")
                    padded.append(jnp.pad(b, (0, (-ni) % p.block_n)))
                b2 = jnp.concatenate(padded)
            else:
                b2 = jnp.asarray(bias, jnp.float32).reshape(-1)
                _check(b2.shape[0] == p.n,
                       f"bias width {b2.shape[0]} vs plan N={p.n}")
            b2 = jnp.pad(b2, (0, n_pad - b2.shape[0]))
        if residual is not None:
            r2 = residual.reshape(-1, residual.shape[-1])
            _check(r2.shape == (m, p.n_out),
                   f"residual {tuple(r2.shape)} vs plan ({m},{p.n_out})")
            r2 = _pad_cols(r2.astype(jnp.float32), out_cols)
            if backend.needs_blocks:
                r2 = _pad_rows(r2, p.block_m)
        epi_kw.update(epilogue=spec, bias=b2, residual=r2)

    if quant:
        run_q = backend.run_quant
        _check(run_q is not None,
               f"backend {p.backend!r} has no dequant-fused run "
               f"(register_backend(..., run_quant=)); it cannot execute "
               f"weight_format={p.weight_format!r} plans")
        if isinstance(w, SparseTernaryPackedWeight):
            # static metadata tuple — hashable, so jit-traced dispatch
            # keys the compiled sparse walk per compressed layout
            epi_kw["sparse_layout"] = w.sparse_layout
        y = run_q(x2, w_p, w.scales, weight_format=p.weight_format,
                  block_m=p.block_m, block_n=p.block_n,
                  block_k=p.block_k, out_dtype=out_dtype, **epi_kw)
    else:
        y = backend.run(x2, w_p, block_m=p.block_m, block_n=p.block_n,
                        block_k=p.block_k, out_dtype=out_dtype, **epi_kw)
    return y[:m, :p.n_out].reshape(*lead, p.n_out)


def _execute_grouped(p: GemmPlan, x: jax.Array, w, *, group_sizes,
                     out_dtype=None, layer=None) -> jax.Array:
    backend = _backends.get_backend(p.backend)
    _check(isinstance(w, packing.PackedWeight) and w.groups == p.groups,
           f"grouped plan needs a grouped pack of {p.groups} "
           f"({p.describe()})")
    _check((w.k, w.n, w.block_n, w.block_k, w.n_splits)
           == (p.k, p.n, p.block_n, p.block_k, p.fused_n_splits),
           f"grouped pack {w.shape} vs plan {p.describe()}")
    _check(group_sizes is not None
           and tuple(group_sizes.shape) == (p.groups,),
           f"grouped plan needs group_sizes [{p.groups}]")
    _check(backend.run_grouped is not None,
           f"backend {p.backend!r} has no grouped run "
           f"(register_backend(..., run_grouped=))")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, p.k)
    m = x2.shape[0]
    _check(m == p.m, f"operand M={m} vs plan M={p.m}")
    bank = w.data
    if layer is not None:
        # the layer folded into the group axis: a bitcast of the stack,
        # and group sizes that are zero outside layer ``layer``'s groups
        _check(bank.ndim == 4, f"layer= reads a stacked bank "
                               f"({p.describe()})")
        n_layers, g = bank.shape[:2]
        bank = bank.reshape(n_layers * g, *bank.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), jnp.int32),
            group_sizes.astype(jnp.int32), (layer * g,))
    k_pad = bank.shape[-2]
    if k_pad != p.k:
        x2 = _pad_cols(x2, k_pad)
    y = backend.run_grouped(x2, bank, group_sizes, out_dtype=out_dtype,
                            epilogue=p.epilogue)
    return y[:, :p.n_out].reshape(*lead, p.n_out)


def split_fused(p: GemmPlan, y: jax.Array) -> tuple:
    """Slice a fused execute()'s output into its logical parts.

    The split map is static: part ``i`` starts at the sum of the earlier
    parts' PADDED widths (each padded to ``p.block_n`` at pack time) and
    is ``p.fused_n_splits[i]`` columns wide.  XLA fuses these slices into
    the consumers, so the split costs nothing at run time.
    """
    if not p.fused_n_splits:
        raise ValueError(f"plan carries no fused split map: "
                         f"{p.describe()}")
    if p.glu:
        raise ValueError("glu plans combine their halves in the kernel; "
                         "there is nothing to split")
    outs, off = [], 0
    for ni in p.fused_n_splits:
        outs.append(y[..., off:off + ni])
        off += -(-ni // p.block_n) * p.block_n
    return tuple(outs)


def pack_for_plan(p: GemmPlan, w: jax.Array, *, transposed: bool | None = None,
                  dtype=None, sharding=None) -> packing.PackedWeight:
    """Pack ``w`` once with exactly the blocking the plan will execute
    (the load-time side of the ``prepack`` lever)."""
    return packing.pack(
        w, transposed=p.transposed if transposed is None else transposed,
        block_n=p.block_n, block_k=p.block_k, dtype=dtype,
        sharding=sharding)


def validate_plan(p: GemmPlan) -> bool:
    """Run (memoized) the autotune bit-exactness gate on the plan's block
    triple — and its epilogue, if any: the fused interpret-mode kernel
    must be bit-identical to the unfused ``kernel -> jnp epilogue``
    sequence (plain plans keep the ``kernels/ref.gemm_blocked`` oracle;
    split-K plans gate against ``kernels/ref.gemm_splitk`` — per-slice
    blocked partials combined by the shared fixed-order tree).

    A QUANTIZED plan swaps the bit-exact gate for the two-part quant
    contract (docs/quantization.md): (1) the error-ledger tolerance gate
    — if the ledger holds an entry for this (n, k, format) whose
    measured max-rel error vs the fp32 oracle exceeds the format's
    declared tolerance, the plan is REJECTED; (2) the structural gate —
    the dequant-fused interpret kernel must stay bit-identical to
    ``gemm_blocked`` (``gemm_splitk`` for split-K plans) over the
    dequantized panels, so the tolerance spent on the format is never
    silently spent twice by the kernel.
    """
    if p.quantized:
        from repro.quant import ledger as _ledger
        from repro.quant.kernels import quant_gate
        ent = _ledger.lookup(p.n, p.k, p.weight_format)
        if ent is not None and not ent.within_tol:
            return False
        return quant_gate(p.block_m, p.block_n, p.block_k,
                          p.weight_format, epilogue=p.epilogue,
                          split_k=p.split_k, sparse=p.sparse)
    return _bitexact_gate(p.block_m, p.block_n, p.block_k,
                          epilogue=p.epilogue, split_k=p.split_k)
