"""Quantized pre-pack formats — paper lever 2 extended below fp32.

The paper's load-issue-bound microbenchmark (610-680 GFLOPS once operand
loads interleave with the FMA stream) says the one lever left *inside*
the inner loop is bytes-loaded-per-tile.  These formats shrink the
packed weight the kernel streams:

  * ``int8``    — per-output-channel symmetric: one fp32 scale per
                  logical column, codes in [-127, 127].  4x fewer weight
                  bytes per tile than fp32.
  * ``ternary`` — 2-bit codes in {-1, 0, +1} + per-column scale
                  (TWN-style threshold, sparse-aware: the zero fraction
                  is recorded on the pack), four codes packed per byte
                  along K.  16x fewer weight bytes per tile than fp32.

Both are *pack-time* formats: ``core.packing.pack(quant=...)`` /
``pack_fused(quant=...)`` produce a :class:`QuantizedPackedWeight` once
at model load, and the dequant-fused kernel (``quant/kernels``)
dequantizes tiles into registers on the way to the fp32 accumulator.
Scale granularity is one scale per (output column, ``GROUP_K``-row K
group) — the production grouping of GGUF-class formats, and the reason
the error stays well inside the ledger tolerance at paper-scale K.
``GROUP_K`` divides every ``block_k`` the policy can resolve (both are
128-multiples), so a kernel tile never straddles a scale group, by
construction.

Reduced precision is done *honestly*, the way the paper reports BNNS
Graph's per-shape error: every concrete pack is measured against its
fp32 oracle and recorded in the error ledger (``quant/ledger``), which
ENFORCES the per-format tolerance at pack time.  Abstract packs
(``jax.eval_shape`` for sharding resolution) skip the measurement — no
values exist to measure.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackedWeight, fit_block

FORMATS = ("int8", "ternary")

# TWN threshold factor: codes are 0 where |w| <= TERNARY_DELTA * mean|w|
# (the sparse-aware split of the ETH ternary-GEMM paper); the per-group
# scale is the mean magnitude of the surviving weights.
TERNARY_DELTA = 0.7

# K rows per scale group (per output column).  128 divides every
# block_k the policy can resolve (fit_block/_fit_vmem bottom out at the
# 128 lane), so one kernel K tile spans whole groups — the "tiles never
# straddle a scale group" alignment contract.
GROUP_K = 128

# Zero-group fraction at which a concrete ternary pack compresses by
# default (``quantize_pack(sparse=None)``): the sparse layout must skip
# enough whole GROUP_K K-groups to pay for its bitmap + group-offset
# index and the per-group (vs per-block_k) kernel schedule.  Set well
# above the analytic break-even ``gemm.policy.sparse_threshold()``
# resolves from the t_pred byte model (~0.03), because host dot kernels
# are not monotone in K (a CPU density sweep found a 1024x1024 shape
# whose compacted K' = 768 dot ran slower than the full K = 1024 dot).
# That CPU reading is the threshold's only grounds; it awaits a chip
# measurement (ROADMAP debt 4).  measured_autotune can still override
# the arm per shape.
SPARSE_DENSITY_THRESHOLD = 0.3

# Four packed zero codes (code 0 stores as crumb 0b01): the byte value
# an all-zero ternary K-run packs to — also what pack padding packs to,
# so padded tail groups compress away like real zero groups.
_TERNARY_ZERO_BYTE = 0x55


def density_bucket_of(group_sparsity: float) -> int:
    """Plan-key bucket for a sparse pack's zero-group fraction:
    ``floor(gs * 10)`` clamped to 0..9.  ``-1`` (negative input) is the
    dense arm's sentinel — a plan is sparse iff its bucket is >= 0."""
    if group_sparsity < 0:
        return -1
    return min(9, max(0, int(group_sparsity * 10.0)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedPackedWeight(PackedWeight):
    """A weight quantized AND packed once at load (see module docstring).

    Subclasses :class:`~repro.core.packing.PackedWeight` so it flows
    through every existing consumer (``layers.linear``, ``fused_linear``,
    the packed-head branch, sharding walks); ``gemm.execute`` dispatches
    it to the backend's dequant-fused run.

    data:   codes — int8 ``[..., K_pad, N_pad]`` for ``int8``;
            uint8 ``[..., K_pad // 4, N_pad]`` for ``ternary`` (four
            2-bit codes per byte along K, code = value + 1).
    scales: fp32 ``[..., K_pad // GROUP_K, N_pad]`` per-(column,
            K-group) scales (all-padding groups carry scale 0 and codes
            0, so padded tiles dequantize to exact 0).
    fmt:    ``"int8"`` | ``"ternary"`` (static; rides onto the plan as
            ``weight_format``).
    sparsity: fraction of zero codes (ternary's sparse-aware stat;
            -1.0 when packed from abstract values).
    """
    scales: jax.Array | None = None
    fmt: str = dataclasses.field(default="int8",
                                 metadata=dict(static=True))
    sparsity: float = dataclasses.field(default=-1.0,
                                        metadata=dict(static=True))

    @property
    def k_pad(self) -> int:
        """Padded contraction depth (the codes' K rows, unpacked)."""
        rows = self.data.shape[-2]
        return rows * 4 if self.fmt == "ternary" else rows

    @property
    def n_pad(self) -> int:
        return self.data.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseTernaryPackedWeight(QuantizedPackedWeight):
    """Compressed ternary pack: all-zero ``GROUP_K`` K-groups removed.

    The dense ternary layout streams every 2-bit code; at group-level
    sparsity the compressed layout stores only the K-groups that carry a
    nonzero code ANYWHERE (the union across column blocks and stacked
    layers, so one column layout serves every panel), plus two static
    index structures:

    data:       uint8 ``[..., occ * (GROUP_K // 4), N_pad]`` — the
                surviving code groups, dense-packed in ascending original
                order (``occ = len(group_index)``).
    scales:     fp32 ``[..., occ, N_pad]`` — the survivors' scale rows.
    k_groups:   total LOGICAL padded groups (``k_pad // GROUP_K``) —
                ``k_pad`` derives from this, NOT from the compacted rows.
    group_index:   surviving original group ids, ascending.
    group_offsets: original group id -> compacted slot, -1 when removed
                (the group-offset index; inverse of ``group_index``).
    occ_bitmap: one int bitmask per ``block_n`` column block, bit ``g``
                set iff group ``g`` has a nonzero code in that block —
                the per-(column-block, K-group) occupancy the sparse
                kernel's per-panel skip reads.

    Round-trip with the dense layout is exact by construction: a removed
    group is all ``0x55`` bytes (four zero codes) with all-zero scale
    rows, which is exactly what :func:`decompress_ternary` re-inserts.
    Flows through ``pack_for_inference``, stacked ``[L, K, N]`` packs
    and fused split maps unchanged — it subclasses the dense pack and
    keeps every inherited field's meaning.
    """
    k_groups: int = dataclasses.field(default=0,
                                      metadata=dict(static=True))
    group_index: tuple = dataclasses.field(default=(),
                                           metadata=dict(static=True))
    group_offsets: tuple = dataclasses.field(default=(),
                                             metadata=dict(static=True))
    occ_bitmap: tuple = dataclasses.field(default=(),
                                          metadata=dict(static=True))

    @property
    def k_pad(self) -> int:
        """LOGICAL padded contraction depth (what the activations pad
        to) — the compacted codes hold fewer rows than this."""
        return self.k_groups * GROUP_K

    @property
    def occupied(self) -> int:
        return len(self.group_index)

    @property
    def group_sparsity(self) -> float:
        """Zero-group fraction — the quantity
        ``SPARSE_DENSITY_THRESHOLD`` thresholds."""
        if not self.k_groups:
            return 0.0
        return 1.0 - len(self.group_index) / self.k_groups

    @property
    def density(self) -> float:
        """Occupied-group fraction: effective weight bytes / dense."""
        return 1.0 - self.group_sparsity

    @property
    def density_bucket(self) -> int:
        """Plan-key bucket (0..9) — rides onto the plan so the sparse
        arm is cache-keyed separately per density decile."""
        return density_bucket_of(self.group_sparsity)

    @property
    def index_bytes(self) -> int:
        """Bytes of sparse metadata the kernel reads alongside the code
        tiles: the occupancy bitmaps + the group-offset index (int32
        slots) — the overhead side of the analytic threshold."""
        nb = max(1, self.n_pad // self.block_n)
        return nb * ((self.k_groups + 7) // 8) + 4 * self.k_groups

    @property
    def sparse_layout(self) -> tuple:
        """Hashable static descriptor of the compressed geometry —
        ``(k_groups, group_index, occ_bitmap, block_n)`` — the backends
        key their jitted sparse runs on it and rebuild the group-walk
        constants from it."""
        return (self.k_groups, self.group_index, self.occ_bitmap,
                self.block_n)


class QuantFormatError(ValueError):
    pass


def _check_fmt(fmt: str):
    if fmt not in FORMATS:
        raise QuantFormatError(
            f"unknown quant format {fmt!r}; choose from {FORMATS}")


def weight_itemsize(fmt: str | None) -> float:
    """Bytes per weight element the kernel streams (the VMEM-budget and
    bytes-per-tile model): fp32 4.0, int8 1.0, ternary 0.25."""
    if fmt in (None, "fp32"):
        return 4.0
    _check_fmt(fmt)
    return 1.0 if fmt == "int8" else 0.25


def _is_concrete(x) -> bool:
    return isinstance(x, np.ndarray) or (
        isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer))


# ------------------------------------------------------------ quantizers
def _grouped(w: jax.Array):
    """``[..., K, N]`` -> (``[..., Kg, GROUP_K, N]`` zero-padded view,
    pad rows added).  Group stats run over axis -2 of the view."""
    k = w.shape[-2]
    pk = (-k) % GROUP_K
    if pk:
        w = _pad_tail(w, pk, 0, w.ndim)
    kg = w.shape[-2] // GROUP_K
    return w.reshape(*w.shape[:-2], kg, GROUP_K, w.shape[-1]), pk


def _ungroup(codes_g: jax.Array, k: int) -> jax.Array:
    out = codes_g.reshape(*codes_g.shape[:-3],
                          codes_g.shape[-3] * GROUP_K, codes_g.shape[-1])
    return out[..., :k, :]


def expand_scales(scales: jax.Array, k: int) -> jax.Array:
    """Broadcast group scales ``[..., Kg, N]`` to per-row ``[..., k, N]``
    — the ONE expansion shared by the kernel tile path, the xla dequant
    run, and the oracle (bitwise-identical values either way)."""
    return jnp.repeat(scales, GROUP_K, axis=-2)[..., :k, :]


def quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Group-wise symmetric int8 for ``w[..., K, N]``: one scale per
    (output column, GROUP_K-row K group) — the GGUF-class production
    grouping.

    Returns (codes int8 ``[..., K, N]``, scales fp32 ``[..., ceil(K /
    GROUP_K), N]``).  Codes are ``round(w / scale)`` with ``scale =
    max_group |w| / 127`` — by construction ``|codes| <= 127`` and the
    round-trip error per element is bounded by its group's ``scale /
    2``.  All-zero groups get scale 0 / codes 0.
    """
    w = jnp.asarray(w, jnp.float32)
    k = w.shape[-2]
    g, _ = _grouped(w)
    amax = jnp.max(jnp.abs(g), axis=-2, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(g / safe), -127, 127).astype(jnp.int8)
    return _ungroup(q, k), scale[..., 0, :].astype(jnp.float32)


def quantize_ternary(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """TWN-style ternary for ``w[..., K, N]``: codes in {-1, 0, +1}
    (int8, NOT yet 2-bit packed — see :func:`pack_ternary_codes`) and a
    per-(column, K-group) fp32 scale.

    Threshold ``delta = TERNARY_DELTA * mean_group |w|`` zeroes the
    small weights (the sparse-aware split); the scale is the mean
    magnitude of the survivors, the L2-optimal reconstruction for that
    support.
    """
    w = jnp.asarray(w, jnp.float32)
    k = w.shape[-2]
    g, _ = _grouped(w)
    mag = jnp.abs(g)
    # group mean over the LOGICAL rows only (a padded tail group must
    # not dilute the threshold of its real rows)
    kg = g.shape[-3]
    last_real = k - (kg - 1) * GROUP_K          # rows of the tail group
    counts = jnp.full((kg, 1, 1), GROUP_K,
                      jnp.float32).at[-1, 0, 0].set(float(last_real))
    delta = (TERNARY_DELTA
             * jnp.sum(mag, axis=-2, keepdims=True) / counts)
    mask = mag > delta
    t = jnp.where(mask, jnp.sign(g), 0.0).astype(jnp.int8)
    cnt = jnp.sum(mask, axis=-2, keepdims=True)
    s = jnp.where(cnt > 0,
                  jnp.sum(jnp.where(mask, mag, 0.0), axis=-2,
                          keepdims=True) / jnp.maximum(cnt, 1),
                  0.0)
    return _ungroup(t, k), s[..., 0, :].astype(jnp.float32)


def quantize(w: jax.Array, fmt: str) -> tuple[jax.Array, jax.Array]:
    _check_fmt(fmt)
    return quantize_int8(w) if fmt == "int8" else quantize_ternary(w)


# ------------------------------------------------- ternary 2-bit packing
def pack_ternary_codes(t: jax.Array) -> jax.Array:
    """Pack ternary codes ``[..., K, N]`` (K % 4 == 0) into uint8
    ``[..., K // 4, N]`` — four consecutive K rows per byte, row ``4r+i``
    in bits ``[2i, 2i+2)``, stored as ``code + 1`` in {0, 1, 2}."""
    k = t.shape[-2]
    if k % 4:
        raise QuantFormatError(f"ternary packing needs K % 4 == 0; got "
                               f"K={k} (pad to the block first)")
    c = (t.astype(jnp.int32) + 1).astype(jnp.uint8)
    c4 = c.reshape(*t.shape[:-2], k // 4, 4, t.shape[-1])
    out = c4[..., 0, :]
    for i in range(1, 4):
        out = out | (c4[..., i, :] << (2 * i))
    return out


def unpack_ternary_codes(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_ternary_codes` — fp32 codes in {-1, 0, +1},
    ``[..., K, N]``.  The ONE unpack definition shared by the kernel tile
    path, the xla dequant run, and the oracle, so all three see
    elementwise-identical values (exact small integers)."""
    # widen before the shifts: Mosaic has no uint8 -> float32 cast, and
    # the crumbs are exact small integers in any integer width
    wide = packed.astype(jnp.int32)
    parts = [((wide >> (2 * i)) & 3).astype(jnp.float32) - 1.0
             for i in range(4)]
    stacked = jnp.stack(parts, axis=-2)          # [..., K//4, 4, N]
    return stacked.reshape(*packed.shape[:-2], packed.shape[-2] * 4,
                           packed.shape[-1])


# ------------------------------------------------------------ dequantize
def dequantize_padded(data: jax.Array, scales: jax.Array,
                      fmt: str) -> jax.Array:
    """Dequantize packed codes back to the padded fp32 panel layout
    ``[..., K_pad, N_pad]`` — elementwise the same ops the kernel applies
    per tile (codes -> fp32, times the group-expanded scales), so the
    full dequant is bit-identical to the tiled one."""
    _check_fmt(fmt)
    if fmt == "ternary":
        codes = unpack_ternary_codes(data)
    else:
        codes = data.astype(jnp.float32)
    return codes * expand_scales(scales.astype(jnp.float32),
                                 codes.shape[-2])


def dequantize(qpw: QuantizedPackedWeight) -> jax.Array:
    """Padded fp32 panels for a quantized pack (the dequant-then-sgemm
    baseline operand; also the error-ledger oracle's weight).  A sparse
    pack decompresses first — the oracle always sees the full logical
    ``[K_pad, N_pad]`` panel layout."""
    if isinstance(qpw, SparseTernaryPackedWeight):
        qpw = decompress_ternary(qpw)
    return dequantize_padded(qpw.data, qpw.scales, qpw.fmt)


# ----------------------------------------------- sparse ternary layout
def _group_occupancy(data: np.ndarray, block_n: int) -> np.ndarray:
    """``[kg, nb]`` bool: does (K-group, column block) hold any nonzero
    code?  Union over stacked leading dims — one column layout must
    serve every layer of a stacked pack."""
    rpg = GROUP_K // 4                       # packed code rows per group
    rows, n_pad = data.shape[-2], data.shape[-1]
    if rows % rpg or n_pad % block_n:
        raise QuantFormatError(
            f"codes {data.shape} not aligned to GROUP_K={GROUP_K} groups "
            f"/ block_n={block_n} panels — compress packed weights only")
    kg, nb = rows // rpg, n_pad // block_n
    g = data.reshape(-1, kg, rpg, nb, block_n)
    return (g != _TERNARY_ZERO_BYTE).any(axis=(0, 2, 4))


def compress_ternary(qpw: QuantizedPackedWeight) -> "SparseTernaryPackedWeight":
    """Dense ternary pack -> compressed layout (see the subclass doc).

    Host-side one-time scan at pack time: groups whose codes are all
    zero in EVERY column block (and every stacked layer) are dropped
    from ``data``/``scales``; the occupancy bitmap additionally records
    which surviving groups each column block can skip.  Refuses packs
    whose removed groups carry nonzero scales (cannot round-trip) —
    ``quantize_pack`` never produces those (all-zero groups get scale
    0), so this only fires on hand-built packs.
    """
    if qpw.fmt != "ternary":
        raise QuantFormatError(
            f"sparse layout is ternary-only; got {qpw.fmt!r}")
    if isinstance(qpw, SparseTernaryPackedWeight):
        return qpw
    if not (_is_concrete(qpw.data) and _is_concrete(qpw.scales)):
        raise QuantFormatError(
            "cannot compress an abstract pack (jax.eval_shape) — no "
            "code values exist to scan for occupancy")
    data = np.asarray(qpw.data)
    scales = np.asarray(qpw.scales)
    rpg = GROUP_K // 4
    occ = _group_occupancy(data, qpw.block_n)    # [kg, nb]
    kg, nb = occ.shape
    n_pad = data.shape[-1]
    lead = data.shape[:-2]
    occ_any = occ.any(axis=1)
    gidx = [int(i) for i in np.nonzero(occ_any)[0]]
    offs = np.full((kg,), -1, np.int64)
    offs[gidx] = np.arange(len(gidx))
    bitmap = tuple(
        int(sum(1 << g for g in range(kg) if occ[g, b]))
        for b in range(nb))
    removed = [g for g in range(kg) if not occ_any[g]]
    if removed and np.any(scales[..., removed, :] != 0):
        raise QuantFormatError(
            "pack has all-zero code groups with nonzero scales; the "
            "compressed layout cannot round-trip them (quantize_pack "
            "gives zero-code groups scale 0)")
    cd = data.reshape(*lead, kg, rpg, n_pad)[..., gidx, :, :]
    cd = cd.reshape(*lead, len(gidx) * rpg, n_pad)
    cs = scales[..., gidx, :]
    return SparseTernaryPackedWeight(
        data=jnp.asarray(cd), n=qpw.n, k=qpw.k, block_n=qpw.block_n,
        block_k=qpw.block_k, n_splits=qpw.n_splits,
        scales=jnp.asarray(cs), fmt="ternary", sparsity=qpw.sparsity,
        k_groups=kg, group_index=tuple(gidx),
        group_offsets=tuple(int(v) for v in offs), occ_bitmap=bitmap)


def decompress_ternary(spw: "SparseTernaryPackedWeight") \
        -> QuantizedPackedWeight:
    """Exact inverse of :func:`compress_ternary`: re-insert all-zero
    code groups (bytes ``0x55``) and zero scale rows at the removed
    slots — bit-for-bit the dense pack the sparse one was built from."""
    rpg = GROUP_K // 4
    data = np.asarray(spw.data)
    scales = np.asarray(spw.scales)
    lead = data.shape[:-2]
    n_pad = data.shape[-1]
    kg, occ = spw.k_groups, spw.occupied
    full = np.full((*lead, kg, rpg, n_pad), _TERNARY_ZERO_BYTE, np.uint8)
    if occ:
        full[..., list(spw.group_index), :, :] = \
            data.reshape(*lead, occ, rpg, n_pad)
    fs = np.zeros((*lead, kg, n_pad), scales.dtype)
    if occ:
        fs[..., list(spw.group_index), :] = scales
    return QuantizedPackedWeight(
        data=jnp.asarray(full.reshape(*lead, kg * rpg, n_pad)),
        n=spw.n, k=spw.k, block_n=spw.block_n, block_k=spw.block_k,
        n_splits=spw.n_splits, scales=jnp.asarray(fs), fmt="ternary",
        sparsity=spw.sparsity)


def _maybe_compress(qpw: QuantizedPackedWeight, sparse: bool | None):
    """The pack-time arm decision.  ``sparse=None`` (auto): compress a
    concrete ternary pack iff its zero-group fraction reaches
    ``SPARSE_DENSITY_THRESHOLD``; ``True`` forces the layout, ``False``
    pins dense.  Abstract packs (eval_shape) never compress — forcing
    one is an error, auto quietly keeps dense (real TWN packs sit near
    group-sparsity 0, so the auto arm leaves today's packs untouched)."""
    if sparse is False:
        return qpw
    if qpw.fmt != "ternary":
        if sparse:
            raise QuantFormatError(
                f"sparse layout is ternary-only; got {qpw.fmt!r}")
        return qpw
    if not (_is_concrete(qpw.data) and _is_concrete(qpw.scales)):
        if sparse:
            raise QuantFormatError(
                "sparse=True needs concrete weights (abstract packs "
                "have no codes to scan)")
        return qpw
    if sparse is None:
        occ = _group_occupancy(np.asarray(qpw.data), qpw.block_n)
        gs = 1.0 - occ.any(axis=1).mean()
        if gs < SPARSE_DENSITY_THRESHOLD:
            return qpw
    return compress_ternary(qpw)


# ------------------------------------------------------------- packing
def _pad_tail(x: jax.Array, pk: int, pn: int, ndim: int) -> jax.Array:
    if not (pk or pn):
        return x
    cfg = [(0, 0)] * (ndim - 2) + [(0, pk), (0, pn)]
    return jnp.pad(x, cfg)


def _sparsity(t) -> float:
    """Zero fraction of LOGICAL codes (callers pass pre-padding arrays —
    pack padding must not inflate the stat).  Device-side reduction: only
    the scalar crosses to host."""
    if not _is_concrete(t):
        return -1.0
    return float(jnp.mean((t == 0).astype(jnp.float32)))


def _fit_group_block_k(k: int, block_k: int | None) -> int:
    """Resolve a pack's block_k honoring BOTH contracts: it divides the
    padded K (fit_block) and spans whole GROUP_K scale groups (the
    tiles-never-straddle-a-group alignment the kernel asserts).  A
    requested value that fit_block keeps but GROUP_K does not divide
    (e.g. 192) rounds down to the next GROUP_K multiple — rounding down
    keeps the kernel grid exact because the pack pads K to whatever
    multiple this returns."""
    from repro.kernels import panel_gemm as _kernel
    bk = fit_block(k, block_k or _kernel.DEFAULT_BLOCK_K)
    if bk % GROUP_K:
        bk = max(GROUP_K, (bk // GROUP_K) * GROUP_K)
    return bk


def quantize_pack(
    w: jax.Array,
    fmt: str,
    *,
    transposed: bool = False,
    block_n: int | None = None,
    block_k: int | None = None,
    sharding=None,
    measure: bool = True,
    sparse: bool | None = None,
) -> QuantizedPackedWeight:
    """Quantize + pack ``w[..., K, N]`` (or ``[..., N, K]`` with
    ``transposed``) once at model load.  Leading dims (stacked ``[L, K,
    N]`` scan weights) ride through untouched.

    Quantization runs on the LOGICAL weight (padding never pollutes a
    group's scale), then codes pad with 0 and scales with 0 so padded
    tiles dequantize to exact zero.  ``measure=True`` (default) records
    the pack's error vs the fp32 oracle in the error ledger and enforces
    the per-format tolerance — skipped automatically for abstract
    weights (``jax.eval_shape``).  ``sparse`` picks the ternary storage
    layout (see :func:`_maybe_compress`): ``None`` auto-compresses at
    ``SPARSE_DENSITY_THRESHOLD`` group sparsity.
    """
    _check_fmt(fmt)
    from repro.kernels import panel_gemm as _kernel
    if transposed:
        w = jnp.swapaxes(w, -1, -2)
    k, n = int(w.shape[-2]), int(w.shape[-1])
    block_k = _fit_group_block_k(k, block_k)
    block_n = fit_block(n, block_n or _kernel.DEFAULT_BLOCK_N)
    q, s = quantize(w, fmt)
    sparsity = _sparsity(q) if fmt == "ternary" else -1.0
    pk, pn = (-k) % block_k, (-n) % block_n
    q = _pad_tail(q, pk, pn, q.ndim)
    s = _pad_tail(s, q.shape[-2] // GROUP_K - s.shape[-2], pn, s.ndim)
    data = pack_ternary_codes(q) if fmt == "ternary" else q
    qpw = QuantizedPackedWeight(data=data, n=n, k=k, block_n=block_n,
                                block_k=block_k, scales=s, fmt=fmt,
                                sparsity=sparsity)
    qpw = _maybe_compress(qpw, sparse)
    if sharding is not None:
        qpw = dataclasses.replace(qpw,
                                  data=jax.device_put(qpw.data, sharding))
    if measure and _is_concrete(w):
        from repro.quant import ledger
        ledger.measure(w, qpw, enforce=True)
    return qpw


def quantize_pack_fused(
    parts,
    fmt: str,
    *,
    transposed: bool = False,
    block_n: int | None = None,
    block_k: int | None = None,
    sharding=None,
    measure: bool = True,
    sparse: bool | None = None,
) -> QuantizedPackedWeight:
    """Horizontal fusion (``core.packing.pack_fused``) in a quantized
    format: each same-K part is quantized per its own output columns,
    padded to a ``block_n`` multiple, and concatenated along N — the
    static split map is preserved, tiles never straddle parts OR scale
    groups, and a glu pair's two column halves stay block-addressable.
    ``sparse`` behaves as in :func:`quantize_pack`; compression runs on
    the fused concat, so the group union spans every part."""
    _check_fmt(fmt)
    from repro.kernels import panel_gemm as _kernel
    ws = [jnp.swapaxes(w, -1, -2) if transposed else w for w in parts]
    if len(ws) < 2:
        raise ValueError("quantize_pack_fused needs at least two weights; "
                         "use quantize_pack for one")
    k = int(ws[0].shape[-2])
    if any(w.shape[-2] != k or w.ndim != ws[0].ndim for w in ws):
        raise ValueError(
            f"fused parts must share K and rank; got "
            f"{[tuple(w.shape) for w in ws]}")
    block_k = _fit_group_block_k(k, block_k)
    bn = min(fit_block(int(w.shape[-1]), block_n or _kernel.DEFAULT_BLOCK_N)
             for w in ws)
    n_splits = tuple(int(w.shape[-1]) for w in ws)
    pk = (-k) % block_k
    qs, ss, zeros, elems = [], [], 0.0, 0
    for w in ws:
        q, s = quantize(w, fmt)
        if fmt == "ternary" and _is_concrete(q):
            zeros += _sparsity(q) * q.size      # logical codes only
            elems += q.size
        pn = (-int(w.shape[-1])) % bn
        q = _pad_tail(q, pk, pn, q.ndim)
        qs.append(q)
        ss.append(_pad_tail(s, q.shape[-2] // GROUP_K - s.shape[-2],
                            pn, s.ndim))
    codes = jnp.concatenate(qs, axis=-1)
    scales = jnp.concatenate(ss, axis=-1)
    sparsity = (zeros / elems) if elems else -1.0
    data = pack_ternary_codes(codes) if fmt == "ternary" else codes
    qpw = QuantizedPackedWeight(
        data=data, n=int(codes.shape[-1]), k=k, block_n=bn,
        block_k=block_k, n_splits=n_splits, scales=scales, fmt=fmt,
        sparsity=sparsity)
    qpw = _maybe_compress(qpw, sparse)
    if sharding is not None:
        qpw = dataclasses.replace(qpw,
                                  data=jax.device_put(qpw.data, sharding))
    if measure and all(_is_concrete(w) for w in ws):
        from repro.quant import ledger
        ledger.measure(jnp.concatenate(
            [_pad_tail(w, 0, (-int(w.shape[-1])) % bn, w.ndim)
             for w in ws], axis=-1), qpw, enforce=True)
    return qpw
