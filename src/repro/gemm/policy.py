"""Shape-resolved dispatch policy + the LRU plan cache.

The paper's structural finding (§4, Table 3): all the headroom over
Accelerate lives in two deployment-level levers chosen *per shape*:

  =============  =============================  =========================
  shape class    winning lever                  plan it resolves to
  =============  =============================  =========================
  K >= N         fine multi-thread panels       ``lever="fine_panels"``:
                 (QKV / FFN-down class — the    block_n sized for grid
                 idle-second-block failure of   occupancy by the
                 coarse panels, paper Fig. 2)   scheduler model;
                                                per-call pack acceptable
  N > K          pre-packed weights             ``lever="prepack"``:
                 (FFN-up / LM-head class —      deep-K blocks
                 the per-call transpose+pad     (Kc = 2048 analogue),
                 dominates, paper §3.2)         weight packed at load
  =============  =============================  =========================

``plan()`` resolves those levers once per ``(shape, dtype, sharding,
backend)`` and memoizes the result in a bounded LRU cache, so the policy
runs at model load / first trace, never per call — the plan-then-execute
separation of BNNS Graph, with the plan inspectable.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import warnings
from typing import Any, Iterator

import jax.numpy as jnp
import numpy as np

import jax

from repro.core import bitexact, packing, scheduler
from repro.gemm import backends as _backends
from repro.gemm import plan_store as _plan_store
from repro.gemm.plan import (EpilogueSpec, GemmPlan, LEVER_FINE_PANELS,
                             LEVER_PREPACK, PACK_NONE, PACK_PERCALL,
                             PACK_PREPACKED)
from repro.kernels import panel_gemm as _kernel
from repro.obs import spans as _spans

# Occupancy target of the fine-panel lever: the paper tunes panels against
# the two AMX blocks; the TPU analogue scores candidates against this many
# parallel compute units.
DEFAULT_NUM_CORES = 8

# Column-panel widths the fine lever considers (the paper's Nc in
# {64..512}); the prepack lever takes the sweep's deployed deep pair.
FINE_BLOCK_N_CANDIDATES = (128, 256, 512)
FINE_BLOCK_K = 512

# Split-K slice counts the decode arm scores (1 = no split).  Resolved
# per (n, k, format) at DECODE_SPLIT_M_REF — NEVER per operand M — so
# every decode-bucket plan for one weight shares one slice map: serve
# (decode at M = slots) and generate (decode at M = batch) must stay
# bit-identical, and split-K changes the accumulation order.
DECODE_SPLIT_K_CANDIDATES = (1, 2, 4, 8)
DECODE_SPLIT_M_REF = 8

_CACHE_MAXSIZE = 512

CacheInfo = collections.namedtuple(
    "CacheInfo", ["hits", "misses", "maxsize", "currsize"])

_cache: "collections.OrderedDict[tuple, GemmPlan]" = collections.OrderedDict()
_cache_lock = threading.Lock()
_grouped: dict = {}      # (groups, in_place, base plan) -> grouped plan
_hits = 0
_misses = 0
# per-key in-flight resolutions (bugfix: two threads missing on one key
# used to both run _resolve — and its bit-exactness/autotune gate —
# outside the lock, double-counting the miss; now the first thread owns
# the resolution and everyone else waits on its Event and counts a hit)
_inflight: dict[tuple, threading.Event] = {}


def plan_cache_info() -> CacheInfo:
    with _cache_lock:
        return CacheInfo(_hits, _misses, _CACHE_MAXSIZE, len(_cache))


def vmem_clamped_count() -> int:
    """How many currently-cached plans had their blocks shrunk to honor
    the kernel VMEM budget (serving observability: surfaced in
    ``GenStats``/``ServeStats``)."""
    with _cache_lock:
        return sum(1 for p in _cache.values() if p.vmem_clamped)


def plan_cache_clear() -> None:
    """Reset the plan cache to a fresh-process state: entries, the
    hit/miss counters ``plan_cache_info`` reports (stale counters make
    warm-start store metrics unreadable), and the clamp warn-state —
    all under the cache lock, atomically with respect to ``plan()``."""
    global _hits, _misses
    with _cache_lock:
        _cache.clear()
        _grouped.clear()
        _hits = _misses = 0
        _vmem_warned.clear()


def _warn_key(p: GemmPlan) -> tuple:
    return (p.m, p.n, p.k, p.dtype, p.backend, p.weight_format)


def _cache_insert(key: tuple, p: GemmPlan) -> None:
    """Insert under the LRU bound.  Bugfix: when a clamped plan is
    evicted, its ``_vmem_warned`` entry is dropped too (unless another
    cached plan still maps to the same warn key) — previously the set
    grew without bound in long-lived serving with many clamped shapes,
    and a re-resolved evicted plan never re-warned."""
    with _cache_lock:
        _cache[key] = p
        _cache.move_to_end(key)
        while len(_cache) > _CACHE_MAXSIZE:
            _, old = _cache.popitem(last=False)
            if old.vmem_clamped:
                wk = _warn_key(old)
                if not any(q.vmem_clamped and _warn_key(q) == wk
                           for q in _cache.values()):
                    _vmem_warned.discard(wk)


def _dtype_name(dtype: Any) -> str:
    return jnp.dtype(dtype).name


def _sharding_key(sharding: Any) -> str:
    return "" if sharding is None else str(sharding)


# -------------------------------------------------------- decode fast lane
_LANE = threading.local()        # per-thread decode-lane scope stack


@contextlib.contextmanager
def decode_lane() -> Iterator[None]:
    """Scope marking every plan resolved inside as a DECODE dispatch.

    The serving engine wraps its decode traces (dense ``decode``, paged
    ``decode_step``, the megastep body) in this scope, exactly like
    ``use_backend``: plans resolved while tracing take the decode policy
    arm — skinny block_m, forced prepack, split-K scored against the
    combine cost — and are plan-keyed separately from prefill plans of
    the same shape.  Prefill paths (one-shot and chunked admission)
    never enter the scope, so their plans and numerics are untouched.
    """
    depth = getattr(_LANE, "depth", 0)
    _LANE.depth = depth + 1
    try:
        yield
    finally:
        _LANE.depth = depth


def in_decode_lane() -> bool:
    """True inside a :func:`decode_lane` scope (trace-time query)."""
    return getattr(_LANE, "depth", 0) > 0


# ------------------------------------------------------------ lever logic
def _fine_block_n(m: int, n: int, k: int, *, block_m: int, block_k: int,
                  num_cores: int) -> int:
    """Occupancy-sized column panel: pick the candidate width whose
    scheduler-predicted time is best (the paper's Fig. 2 sweep, online)."""
    cands = sorted({packing.fit_block(n, c) for c in FINE_BLOCK_N_CANDIDATES})

    def score(bn: int):
        p = scheduler.plan(m, n, k, block_m=block_m, block_n=bn,
                           block_k=block_k, num_cores=num_cores)
        return (p.t_pred, bn)          # tie-break toward finer panels

    return min(cands, key=score)


_vmem_warned: set = set()


def _warn_vmem_clamp(key: tuple, requested: tuple, got: tuple):
    """Satellite: a clamped block triple used to be silent unless the
    caller inspected ``plan.vmem_clamped`` — now the FIRST resolution of
    each clamped plan key warns, naming the key (cleared alongside the
    plan cache so tests can re-arm it)."""
    if key in _vmem_warned:
        return
    _vmem_warned.add(key)
    warnings.warn(
        f"gemm policy clamped the block triple {requested} -> {got} to "
        f"fit the kernel VMEM budget for plan key {key} (the plan "
        f"records this as vmem_clamped=True)", RuntimeWarning,
        stacklevel=3)


def _fit_vmem(bm: int, bn: int, bk: int, dtype: str,
              epilogue: EpilogueSpec | None,
              weight_format: str = "fp32", split_k: int = 1,
              sparse_groups: int = 0, sparse_n: int = 0):
    """Shrink the block triple until ``kernels.panel_gemm.vmem_bytes``
    fits the VMEM budget (satellite: an explicit or fused-wide triple —
    a glu epilogue doubles the weight + accumulator tiles — could
    otherwise exceed it).  Shrinks the deeper of (block_k, block_n)
    first; every candidate still divides the padded dim because padded
    dims are 128-multiples and the shrink path halves toward 128.

    ``weight_format`` re-resolves the budget for quantized packs: int8
    tiles stream 4x and ternary 16x fewer weight bytes, so block
    triples that clamp at fp32 can stand at reduced precision.
    ``split_k`` sizes the decode lane's fp32 partials slab into the
    same budget (the combine epilogue holds every slice's partial for
    one output tile).  ``sparse_groups > 0`` budgets the sparse-ternary
    walk instead of the dense K stream (the kernel's K step is pinned
    at ``GROUP_K`` regardless of block_k); ``sparse_n`` is the logical
    N the occupancy matrix spans, so its per-panel width is re-derived
    as the shrink loop narrows block_n."""
    dt = jnp.dtype(dtype)
    clamped = False
    quant = weight_format != "fp32"
    while _kernel.vmem_bytes(
            bm, bn, bk, dt, epilogue=epilogue,
            weight_format=weight_format, split_k=split_k,
            sparse_groups=sparse_groups,
            sparse_panels=(max(1, -(-sparse_n // bn)) if sparse_groups
                           else 0)) > _kernel.VMEM_BUDGET:
        if bk >= bn and bk > 128:
            bk = max(128, bk // 2)
            if quant and bk % 128:
                # quantized tiles must span whole GROUP_K scale groups;
                # 128 always divides the pack-padded K, so it is the
                # one shrink target that keeps both contracts
                bk = 128
        elif bn > 128:
            bn = max(128, bn // 2)
        elif bm > 8:
            bm = max(8, bm // 2)
        else:
            break                      # minimal blocks; nothing left
        clamped = True
    return bm, bn, bk, clamped


def _decode_split_k(n: int, k: int, k_pad: int, *, block_m: int,
                    block_n: int, block_k: int, dtype: str,
                    num_cores: int, weight_format: str,
                    epilogue: EpilogueSpec | None) -> int:
    """Score the decode arm's split-K candidates and pick the winner.

    Scored at the CANONICAL decode M (``DECODE_SPLIT_M_REF``), not the
    operand M: split-K changes the accumulation order, so the slice map
    must be a pure function of (n, k, blocks, format) — generate
    (decode at M = batch) and serve (decode at M = slots) then resolve
    the same split and stay token-for-token bit-identical.  (The block
    triple this screens against is M-independent too: the decode arm
    pins ``block_m = DECODE_BLOCK_M``.)  Candidates must cut the padded
    K into whole ``block_k`` slices (which keeps quantized slices on
    whole GROUP_K scale groups, since quantized block_k is a GROUP_K
    multiple) and must fit the VMEM budget WITH their partials slab at
    the final, post-clamp blocks — the chosen split never re-triggers
    the clamp.  ``k_pad`` is the contraction depth the operand will
    actually have at dispatch (the caller passes the raw ``k`` for an
    unpadded PACK_NONE operand on a shape-agnostic backend)."""
    best = (float("inf"), 1)
    for s in DECODE_SPLIT_K_CANDIDATES:
        if k_pad % s or (k_pad // s) % block_k:
            continue
        if s > 1 and _kernel.vmem_bytes(
                block_m, block_n, block_k, jnp.dtype(dtype),
                epilogue=epilogue, weight_format=weight_format,
                split_k=s) > _kernel.VMEM_BUDGET:
            continue
        p = scheduler.plan(DECODE_SPLIT_M_REF, n, k, block_m=block_m,
                           block_n=block_n, block_k=block_k,
                           num_cores=num_cores, split_k=s)
        # tie-break toward fewer slices (less combine traffic)
        if (p.t_pred, s) < best:
            best = (p.t_pred, s)
    return best[1]


def _resolve(m: int, n: int, k: int, *, dtype: str, backend: str,
             num_cores: int, block_m: int | None, block_n: int | None,
             block_k: int | None, pack: str | None, transposed: bool,
             sharding_key: str, validate: bool,
             epilogue: EpilogueSpec | None = None,
             fused_n_splits: tuple = (),
             weight_format: str = "fp32", decode: bool = False,
             split_k: int | None = None,
             density_bucket: int = -1) -> GemmPlan:
    sparse = density_bucket >= 0
    if sparse:
        if weight_format != "ternary":
            raise ValueError(
                f"density_bucket={density_bucket} marks the sparse-ternary "
                f"arm; it requires weight_format='ternary' "
                f"(got {weight_format!r})")
        if split_k is not None and int(split_k) != 1:
            raise ValueError(
                f"split_k={split_k} is incompatible with the sparse-ternary "
                f"walk (the group-granular grid has no reduction-side "
                f"slices); sparse plans always carry split_k=1")
        # the sparse walk streams one GROUP_K K-group per grid step and
        # combines per-group partials in group order — a split-K cut of
        # that order would change the accumulation tree, so the arm pins
        # split_k=1 at plan time rather than rejecting at dispatch
        split_k = 1
    bm = block_m or min(_kernel.DEFAULT_BLOCK_M, _rnd_up(m, 8))
    if decode and block_m is None:
        # skinny-M specialization: decode row panels are ONE 8-row
        # sublane tile for every decode M (m > 8 spans several row
        # panels) — never the 128-row prefill panel.  Pinning block_m
        # keeps the whole decode block triple, and therefore the
        # split-K choice screened against it, independent of the
        # operand M (the serve == generate parity requirement).
        bm = _kernel.DECODE_BLOCK_M
    if k >= n:                              # lever 1: fine panels
        lever = LEVER_FINE_PANELS
        default_pack = PACK_PERCALL
        bk = block_k or packing.fit_block(k, FINE_BLOCK_K)
        bn = block_n or _fine_block_n(m, n, k, block_m=bm, block_k=bk,
                                      num_cores=num_cores)
    else:                                   # lever 2: pre-pack, deep K
        lever = LEVER_PREPACK
        default_pack = PACK_PREPACKED
        bk = block_k or packing.fit_block(k, _kernel.DEFAULT_BLOCK_K)
        bn = block_n or packing.fit_block(n, _kernel.DEFAULT_BLOCK_N)
    if decode:
        # decode arm: the per-call pack the fine lever tolerates at
        # M = 128 (amortized over the row panel) is ruinous at M <= 8 —
        # decode is weight-bound, so the re-layout must be paid at load
        default_pack = PACK_PREPACKED
    if weight_format != "fp32":
        from repro.quant.formats import _check_fmt
        _check_fmt(weight_format)
        # quantization is a pack-time format: whatever lever the shape
        # resolves to, the weight must have been quantize-packed at load
        if pack is not None and pack != PACK_PREPACKED:
            raise ValueError(
                f"weight_format={weight_format!r} is a pack-time format; "
                f"it requires pack={PACK_PREPACKED!r} (got {pack!r})")
        default_pack = PACK_PREPACKED
    pack = pack or default_pack
    if pack not in (PACK_PREPACKED, PACK_PERCALL, PACK_NONE):
        raise ValueError(f"unknown pack decision {pack!r}")
    # Clamp the blocks FIRST (with an explicit split_k's partials slab
    # in the footprint), then resolve the policy split against the
    # final triple: _decode_split_k only admits candidates that fit the
    # clamped blocks, so the choice never re-triggers the clamp, and an
    # explicit split_k that the clamp made undivisible fails HERE, at
    # plan time — not as a PlanMismatchError at dispatch.
    req = (bm, bn, bk)
    sparse_groups = 0
    if sparse:
        from repro.quant.formats import GROUP_K
        kg = max(1, -(-k // GROUP_K))
        # VMEM-worst-case occupied-group count the bucket still admits
        # (bucket b certifies zero-group fraction >= b/10)
        sparse_groups = max(1, kg - (kg * density_bucket) // 10)
    bm, bn, bk, clamped = _fit_vmem(bm, bn, bk, dtype, epilogue,
                                    weight_format,
                                    1 if split_k is None else int(split_k),
                                    sparse_groups=sparse_groups,
                                    sparse_n=n)
    if clamped:
        _warn_vmem_clamp((m, n, k, dtype, backend, weight_format), req,
                         (bm, bn, bk))
    grid_backend = _backends.get_backend(backend).needs_blocks
    # the contraction depth the operand will ACTUALLY have at dispatch:
    # PACK_NONE on a shape-agnostic backend skips the re-layout, so its
    # K is never block-padded — the slice validation must use raw k or
    # a plan would pass here and reject at execute()
    k_pad = _rnd_up(k, bk) if (pack != PACK_NONE or grid_backend) else k
    if split_k is None:
        # the split lever targets the PANEL-GRID backends: occupancy is
        # a property of the kernel grid, and a shape-agnostic backend
        # (xla) has no reduction-side grid to fill, so the policy keeps
        # split_k=1 for it.  The only reading behind that rule is a CPU
        # one (a wash-to-loss on the host); it awaits a chip measurement
        # (ROADMAP debt 4).  Explicit split_k= overrides remain
        # available on every backend.
        split_k = (_decode_split_k(n, k, k_pad, block_m=bm, block_n=bn,
                                   block_k=bk, dtype=dtype,
                                   num_cores=num_cores,
                                   weight_format=weight_format,
                                   epilogue=epilogue)
                   if decode and grid_backend else 1)
    split_k = int(split_k)
    if split_k < 1 or k_pad % split_k or (split_k > 1
                                          and (k_pad // split_k) % bk):
        raise ValueError(
            f"split_k={split_k} does not cut the dispatch-time "
            f"K={k_pad} into whole block_k={bk} slices"
            + (" (the VMEM fit clamped the requested blocks to "
               f"{(bm, bn, bk)}; request budget-fitting blocks or a "
               "compatible split)" if clamped else ""))

    weight_density = 1.0
    sparse_index_bytes = 0.0
    if sparse:
        # score the arm at the bucket's midpoint occupied fraction, and
        # charge the occupancy-bitmap + group-offset slab the walk reads
        weight_density = max(0.05, 1.0 - (density_bucket + 0.5) / 10.0)
        nb = max(1, -(-n // bn))
        sparse_index_bytes = float(nb * ((kg + 7) // 8) + 4 * kg)
    sched = scheduler.plan(m, n, k, block_m=bm, block_n=bn, block_k=bk,
                           num_cores=num_cores, split_k=split_k,
                           weight_density=weight_density,
                           sparse_index_bytes=sparse_index_bytes)
    validated = False
    if validate:
        if weight_format != "fp32":
            from repro.quant.kernels import quant_gate
            ok = quant_gate(bm, bn, bk, weight_format, epilogue=epilogue,
                            split_k=split_k, sparse=sparse)
        else:
            ok = _bitexact_gate(bm, bn, bk, epilogue=epilogue,
                                split_k=split_k)
        if not ok:
            raise RuntimeError(
                f"blocks ({bm},{bn},{bk}) failed the bit-exactness gate "
                f"(epilogue={epilogue}, weight_format={weight_format}, "
                f"split_k={split_k}) vs the unfused kernel -> op oracle "
                f"(autotune reject protocol)")
        validated = True
    return GemmPlan(m=m, n=n, k=k, dtype=dtype, backend=backend,
                    block_m=bm, block_n=bn, block_k=bk, pack=pack,
                    lever=lever, t_pred=sched.t_pred,
                    occupancy=sched.occupancy, transposed=transposed,
                    sharding_key=sharding_key, validated=validated,
                    epilogue=epilogue, fused_n_splits=fused_n_splits,
                    vmem_clamped=clamped, weight_format=weight_format,
                    split_k=split_k, decode=decode,
                    density_bucket=density_bucket)


def _rnd_up(x: int, mult: int) -> int:
    return max(mult, ((x + mult - 1) // mult) * mult)


# --------------------------------------- chunked-prefill plan-key buckets
# Static admission widths the continuous-batching pool pads prefill
# chunks to (docs/serving.md).  Plans are keyed on exact M, so a ragged
# stream of chunk tails (1..C rows) would resolve a fresh plan per
# length; padding every chunk to a bucket collapses the whole
# mixed-length request mix onto a handful of stable plan keys — after
# the first admission cycle, ``plan_cache_info().misses`` stops moving.
PREFILL_M_BUCKETS = (8, 16, 32, 64, 128)

# Decode-phase buckets: [slots, 1] decode dispatches at M = slots.  The
# prefill buckets round every M below 8 up to 8, so slot pools of width
# 1, 2 and 4 would alias into ONE plan key and pay padded rows for the
# difference; the decode buckets keep small pools exact (decode is the
# latency-bound phase — padded rows are pure waste there).
DECODE_M_BUCKETS = (1, 2, 4, 8)


def bucket_m(m: int, *, decode: bool = False) -> int:
    """Smallest static chunk bucket holding ``m`` rows.

    ``decode=True`` buckets against ``DECODE_M_BUCKETS`` first, so slot
    pools of width 1..8 each get their own plan key instead of all
    rounding up to the smallest prefill bucket (8) with padded waste.
    Beyond the last bucket: the next multiple of 128, the paper's
    prefill row panel."""
    if m < 1:
        raise ValueError(f"m={m}: need at least one row")
    if decode:
        for b in DECODE_M_BUCKETS:
            if m <= b:
                return b
    for b in PREFILL_M_BUCKETS:
        if m <= b:
            return b
    return _rnd_up(m, 128)


# --------------------------------------------------------- bit-exact gate
_gate_memo: dict[tuple, bool] = {}


def _bitexact_gate(bm: int, bn: int, bk: int, *,
                   epilogue: EpilogueSpec | None = None,
                   reduced_k_blocks: int = 2, seed: int = 0,
                   split_k: int = 1) -> bool:
    """core/autotune's reject protocol for one block triple: the kernel
    (compiled on a TPU, interpreted elsewhere — ``gate_interpret``) on a
    reduced shape with a real K-carry must be BIT-IDENTICAL to the
    blocked oracle.  With an epilogue the oracle is the UNFUSED
    sequence — plain kernel to an fp32 accumulator, then the same jnp
    epilogue ops (``apply_epilogue``) under jit — so the gate covers
    every ``EpilogueSpec``, glu included.  ``split_k > 1`` gates the
    decode lane's split-K kernel against ``ref.gemm_splitk`` — per-slice
    blocked partials combined by the shared fixed-order tree — with the
    reduced K sized so every slice carries a real multi-block K-carry.
    Memoized per (triple, spec, split_k)."""
    key = (bm, bn, bk, epilogue, split_k)
    if key in _gate_memo:
        return _gate_memo[key]
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    interpret = _kernel.gate_interpret()
    glu = epilogue is not None and epilogue.glu is not None
    m_r, k_r = bm, reduced_k_blocks * bk * split_k
    n_r = 2 * bn if glu else bn
    x = jnp.asarray(rng.standard_normal((m_r, k_r)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k_r, n_r)), jnp.float32)
    if split_k > 1:
        def run(bias=None, res=None, spec=None, out_dtype=None):
            return _kernel.panel_gemm_splitk(
                x, w, bias, res, split_k=split_k, block_m=bm, block_n=bn,
                block_k=bk, epilogue=spec, out_dtype=out_dtype,
                interpret=interpret)

        def oracle_acc():
            return ref.gemm_splitk(x, w, bk, split_k,
                                   out_dtype=jnp.float32)
    else:
        def run(bias=None, res=None, spec=None, out_dtype=None):
            return _kernel.panel_gemm(
                x, w, bias, res, block_m=bm, block_n=bn, block_k=bk,
                epilogue=spec, out_dtype=out_dtype, interpret=interpret)

        def oracle_acc():
            return ref.gemm_blocked(x, w, bk, out_dtype=jnp.float32)

    if epilogue is None:
        y = run()
        oracle = oracle_acc().astype(x.dtype)
    else:
        n_out = bn if glu else n_r
        bias = (jnp.asarray(rng.standard_normal((n_r,)), jnp.float32)
                if epilogue.bias else None)
        res = (jnp.asarray(rng.standard_normal((m_r, n_out)), jnp.float32)
               if epilogue.residual else None)
        y = run(bias, res, epilogue)
        oracle = jax.jit(
            lambda a, b, r: _kernel.apply_epilogue(
                a, epilogue, bias=b, residual=r).astype(jnp.float32)
        )(oracle_acc(), bias, res)
    ok = bitexact.bit_identical(np.asarray(y), np.asarray(oracle))
    _gate_memo[key] = ok
    return ok


# ------------------------------------------------------------- public API
def _plan_key(m: int, n: int, k: int, *, dtype: Any = jnp.float32,
              backend: str | None = None,
              num_cores: int = DEFAULT_NUM_CORES,
              block_m: int | None = None, block_n: int | None = None,
              block_k: int | None = None, pack: str | None = None,
              transposed: bool = False, sharding: Any = None,
              validate: bool = False,
              epilogue: EpilogueSpec | None = None,
              fused_n_splits: tuple = (), weight_format: str = "fp32",
              decode: bool = False,
              split_k: int | None = None,
              density_bucket: int = -1, stacked: bool = False) -> tuple:
    """The normalized in-memory cache key for a ``plan()`` request
    (``validate`` at index ``_KEY_VALIDATE_IDX``; the persistent store
    key is this tuple minus that element — see :func:`store_key`).
    ``density_bucket`` is appended LAST so the validate slice below and
    every persisted schema-v1 store key prefix stay position-stable.
    ``stacked`` (the weight is a layer scan's stacked pack) only steers
    the device's default backend, which the key holds."""
    backend = _backends.resolve_backend(backend, m=m,
                                        weight_format=weight_format,
                                        stacked=stacked)
    dtype = _dtype_name(dtype)
    skey = _sharding_key(sharding)
    if epilogue is not None and epilogue.is_noop:
        epilogue = None
    fused_n_splits = tuple(int(s) for s in fused_n_splits)
    return (int(m), int(n), int(k), dtype, backend, num_cores, block_m,
            block_n, block_k, pack, bool(transposed), skey, bool(validate),
            epilogue, fused_n_splits, weight_format, bool(decode), split_k,
            int(density_bucket))


_KEY_VALIDATE_IDX = 12

# Plan-resolution fault hook (chaos testing): ``repro.runtime.faults``
# installs its ``maybe_fire`` here at the first ``use_faults`` entry —
# a hook global rather than an import because the gemm layer must not
# import ``repro.runtime`` at module level.  Called on the plan-cache
# miss path, before the store lookup / analytic resolve; when it
# raises, the in-flight dedup below releases the key so a retrying
# caller resolves cleanly.
_FAULT_HOOK = None


def store_key(m: int, n: int, k: int, **kw) -> str:
    """The persistent-store key for a policy request: the normalized
    cache key minus ``validate`` (a validated entry serves both), as a
    deterministic string.  Same keyword surface as :func:`plan` (minus
    ``validate``); the measured autotuner commits winners under the key
    the later policy-position request (no block overrides) will ask."""
    kw.pop("validate", None)
    key = _plan_key(m, n, k, **kw)
    return repr(key[:_KEY_VALIDATE_IDX] + key[_KEY_VALIDATE_IDX + 1:])


def _store_key_of(cache_key: tuple) -> str:
    return repr(cache_key[:_KEY_VALIDATE_IDX]
                + cache_key[_KEY_VALIDATE_IDX + 1:])


def plan(m: int, n: int, k: int, *, dtype: Any = jnp.float32,
         backend: str | None = None, num_cores: int = DEFAULT_NUM_CORES,
         block_m: int | None = None, block_n: int | None = None,
         block_k: int | None = None, pack: str | None = None,
         transposed: bool = False, sharding: Any = None,
         validate: bool = False, epilogue: EpilogueSpec | None = None,
         fused_n_splits: tuple = (),
         weight_format: str = "fp32", decode: bool | None = None,
         split_k: int | None = None,
         density_bucket: int = -1, stacked: bool = False) -> GemmPlan:
    """Resolve (and cache) the dispatch plan for a ``[m,k] @ [k,n]`` GEMM.

    ``backend=None`` takes the current default (``use_backend`` scope or
    the process default — never the removed ``REPRO_GEMM_IMPL`` env
    var).  Explicit ``block_*`` / ``pack`` override the policy
    (autotune sweeps, baseline paths); ``validate=True`` runs the
    autotune bit-exactness gate on the resolved blocks (and
    ``epilogue`` / ``split_k``, if any) before the plan is issued.
    ``epilogue`` / ``fused_n_splits`` / ``weight_format`` are
    plan-keyed: fused, quantized and plain plans for one shape are
    distinct cache entries.  ``weight_format`` other than ``"fp32"``
    marks a quantized pack-time format (``repro.quant``): the VMEM fit
    uses its bytes-per-element and execute() dispatches the backend's
    dequant-fused run.

    ``decode=None`` reads the ambient :func:`decode_lane` scope (the
    serving engine's decode traces); ``True``/``False`` pin the arm
    explicitly.  Decode plans are plan-keyed separately and take the
    decode policy arm: skinny block_m, forced prepack, and ``split_k``
    resolved by :func:`_decode_split_k` unless given explicitly.

    ``density_bucket >= 0`` resolves the sparse-ternary arm for a
    ``SparseTernaryPackedWeight`` (``weight_format='ternary'`` only):
    the scheduler scores the occupied-group fraction and the index-slab
    overhead, the VMEM fit budgets the group-granular walk, ``split_k``
    is pinned at 1, and the bucket is plan-keyed so sparse and dense
    ternary plans for one shape never alias.

    ``stacked`` says the weight is a layer scan's stacked pack
    (``plan_for_packed`` sets it); with ``backend=None`` it steers the
    device's default (``backends.device_default``).

    When a plan store is active (``gemm.use_plan_store`` scope or the
    process default), an in-memory miss consults the store before
    ``_resolve``: a hit adopts the stored plan — skipping the analytic
    policy and, for entries committed through the bit-exactness gate,
    the gate itself — and every freshly resolved plan is recorded back
    into the store (persist with ``store.save()``).  Concurrent callers
    missing on one key share a single resolution (per-key in-flight
    dedup): the gate and the miss are paid exactly once.
    """
    global _hits, _misses
    if decode is None:
        decode = in_decode_lane()
    key = _plan_key(m, n, k, dtype=dtype, backend=backend,
                    num_cores=num_cores, block_m=block_m, block_n=block_n,
                    block_k=block_k, pack=pack, transposed=transposed,
                    sharding=sharding, validate=validate, epilogue=epilogue,
                    fused_n_splits=fused_n_splits,
                    weight_format=weight_format, decode=decode,
                    split_k=split_k, density_bucket=density_bucket,
                    stacked=stacked)
    (m, n, k, dtype, backend, num_cores, block_m, block_n, block_k, pack,
     transposed, skey, validate, epilogue, fused_n_splits, weight_format,
     decode, split_k, density_bucket) = key
    while True:
        with _cache_lock:
            hit = _cache.get(key)
            if hit is not None:
                _hits += 1
                _cache.move_to_end(key)
                return hit
            ev = _inflight.get(key)
            if ev is None:
                ev = _inflight[key] = threading.Event()
                _misses += 1
                break                       # we own this resolution
        ev.wait()                           # another thread resolves it;
        # loop: adopt its cached plan (a hit), or — if it failed —
        # become the owner ourselves
    try:
        # the plan-cache MISS path only: hits return above without a
        # span, so plan_resolve events in a trace are exactly the plan
        # churn the serving tests watch via plan_cache_info().misses
        with _spans.span("plan_resolve", m=m, n=n, k=k, dtype=dtype,
                         backend=backend, decode=bool(decode)) as span:
            if _FAULT_HOOK is not None:
                _FAULT_HOOK("plan_resolve", m=m, n=n, k=k)
            store = _plan_store.active_plan_store()
            p = None
            if store is not None:
                sp = store.lookup(_store_key_of(key))
                if (sp is not None and sp.shape == (m, n, k)
                        and (not validate or sp.validated)):
                    p = sp
                    span.set(source="plan_store")
            if p is None:
                p = _resolve(m, n, k, dtype=dtype, backend=backend,
                             num_cores=num_cores, block_m=block_m,
                             block_n=block_n, block_k=block_k, pack=pack,
                             transposed=transposed, sharding_key=skey,
                             validate=validate, epilogue=epilogue,
                             fused_n_splits=fused_n_splits,
                             weight_format=weight_format, decode=decode,
                             split_k=split_k,
                             density_bucket=density_bucket)
                span.set(source="policy")
                if store is not None:
                    store.put(_store_key_of(key), p)
            span.set(lever=p.lever, split_k=p.split_k,
                     blocks=f"{p.block_m}x{p.block_n}x{p.block_k}")
            _cache_insert(key, p)
            return p
    finally:
        with _cache_lock:
            _inflight.pop(key, None)
        ev.set()


def _packed_sharding(pw: packing.PackedWeight):
    """The placement a packed weight actually carries, for the plan key.

    Fixes the plan_for_packed aliasing bug: packs placed with distinct
    ``NamedSharding``s used to collapse onto one ``sharding_key=""`` plan
    entry.  Tracers (plan resolution happens at trace time inside jit)
    and plain single-device arrays key as None — the placement-neutral
    default — so cache behavior is unchanged for unsharded runs.
    """
    try:
        s = pw.data.sharding
    except Exception:
        return None
    return s if isinstance(s, jax.sharding.NamedSharding) else None


def plan_for_packed(m: int, pw: packing.PackedWeight, *,
                    backend: str | None = None,
                    num_cores: int = DEFAULT_NUM_CORES,
                    validate: bool = False,
                    epilogue: EpilogueSpec | None = None,
                    decode: bool | None = None) -> GemmPlan:
    """Plan for a weight already packed at model load: the block decision
    was made when the pack happened; the plan adopts it (and still records
    which lever the policy assigns the shape).  A fused pack's static
    split map, a quantized pack's format (``QuantizedPackedWeight.fmt``
    -> ``weight_format``), and the requested ``epilogue`` ride onto the
    plan.  A quantized pack's ``dtype`` keys as the fp32 the dequant
    produces (codes are not an operand dtype).  ``decode=None`` reads
    the ambient :func:`decode_lane` scope (as :func:`plan` does).
    A ``SparseTernaryPackedWeight`` carries its ``density_bucket`` onto
    the plan, selecting the sparse arm.  A grouped pack resolves the
    grouped arm; for a stacked bank the plan also records whether the run
    reads it in place (``GemmPlan.in_place``)."""
    fmt = getattr(pw, "fmt", "fp32")
    dtype = "float32" if fmt != "fp32" else pw.dtype
    if getattr(pw, "groups", 0):
        # the grouped arm: the bank's blocks and the row bucket resolve as
        # for one weight of the bank, and the plan carries the groups; it
        # runs on a backend that has a grouped run.  A stacked bank is
        # read in place where the device default says so (and no scope
        # or argument names the backend)
        in_place = backend is None and _backends.default_grouped_in_place(
            dtype=_dtype_name(dtype), stacked=pw.data.ndim == 4)
        backend = _backends.resolve_backend(backend)
        if _backends.get_backend(backend).run_grouped is None:
            backend = "xla"
        base = plan(m, pw.n, pw.k, dtype=dtype, backend=backend,
                    num_cores=num_cores, block_n=pw.block_n,
                    block_k=pw.block_k, pack=PACK_PREPACKED,
                    sharding=_packed_sharding(pw), epilogue=epilogue,
                    fused_n_splits=pw.n_splits, decode=decode)
        key = (pw.groups, in_place, base)
        with _cache_lock:
            p = _grouped.get(key)
            if p is None:
                p = _grouped[key] = dataclasses.replace(
                    base, groups=pw.groups, in_place=in_place)
        return p
    return plan(m, pw.n, pw.k, dtype=dtype, backend=backend,
                num_cores=num_cores, block_n=pw.block_n,
                block_k=pw.block_k, pack=PACK_PREPACKED, validate=validate,
                sharding=_packed_sharding(pw), epilogue=epilogue,
                fused_n_splits=pw.n_splits, weight_format=fmt,
                decode=decode,
                density_bucket=getattr(pw, "density_bucket", -1),
                stacked=pw.data.ndim == 3)


def pack_blocks(n: int, k: int, *, m_hint: int = 128,
                block_n: int | None = None, block_k: int | None = None,
                num_cores: int = DEFAULT_NUM_CORES,
                epilogue: EpilogueSpec | None = None,
                weight_format: str = "fp32") -> tuple[int, int]:
    """The load-time pack decision, policy-resolved: (block_n, block_k)
    for a [k, n] weight.  ``m_hint`` is the serving M the plan targets
    (the paper's S = 128 prefill row panel).  ``epilogue`` lets a fused
    pack reserve VMEM for its store-phase footprint (a glu epilogue
    doubles the weight/accumulator tiles), and ``weight_format`` sizes
    the streamed tile for quantized packs, so the blocks the pack adopts
    already fit the budget the execute-time plan will enforce."""
    p = plan(m_hint, n, k, block_n=block_n, block_k=block_k,
             num_cores=num_cores, epilogue=epilogue,
             weight_format=weight_format)
    return p.block_n, p.block_k


def sparse_threshold(m: int = 128, n: int = 4096, k: int = 4096, *,
                     num_cores: int = DEFAULT_NUM_CORES) -> float:
    """Analytic break-even zero-group fraction for the sparse arm.

    Sweeps the scheduler model: the dense ternary plan at the policy's
    deep-K blocks vs the sparse walk (``block_k = GROUP_K``, weight
    traffic and compute scaled by the occupied fraction, plus the
    occupancy-bitmap + group-offset slab) — returning the smallest
    zero-group fraction (in hundredths) at which the sparse arm's
    predicted time first wins.  The model's break-even is small (the
    index slab is a few KB against MBs of weight traffic; the real cost
    is the 16x deeper grid the GROUP_K step forces, carried by the
    ``GRID_STEP_OVERHEAD`` term), so the shipped pack-time trigger
    ``quant.SPARSE_DENSITY_THRESHOLD`` (0.3) sits deliberately ABOVE
    it: packs only cross to the compressed layout when the win also
    survives measured launch overheads and the host dot kernels'
    non-monotone-in-K behavior (see the constant's comment), not just
    the napkin model.
    """
    from repro.quant.formats import GROUP_K
    bm = min(_kernel.DEFAULT_BLOCK_M, _rnd_up(m, 8))
    bn = packing.fit_block(n, _kernel.DEFAULT_BLOCK_N)
    bk = packing.fit_block(k, _kernel.DEFAULT_BLOCK_K)
    kg = max(1, -(-k // GROUP_K))
    idx = float(max(1, -(-n // bn)) * ((kg + 7) // 8) + 4 * kg)
    dense = scheduler.plan(m, n, k, block_m=bm, block_n=bn, block_k=bk,
                           num_cores=num_cores).t_pred
    for i in range(1, 100):
        gs = i / 100.0
        t = scheduler.plan(m, n, k, block_m=bm, block_n=bn,
                           block_k=GROUP_K, num_cores=num_cores,
                           weight_density=1.0 - gs,
                           sparse_index_bytes=idx).t_pred
        if t < dense:
            return gs
    return 1.0
