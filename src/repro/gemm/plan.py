"""``GemmPlan`` — the frozen, hashable description of one GEMM dispatch.

A plan is everything the paper decides *above* the inner loop, resolved
once per shape and reused on every call:

  * the operand geometry (m, n, k, dtype),
  * which backend runs the compute loop (``xla`` / ``pallas`` /
    ``interpret`` / anything registered via ``gemm.register_backend``),
  * the panel blocking (block_m, block_n, block_k) — the paper's
    (M, Nc, Kc) levers,
  * the pack decision (``prepack``: pay the weight re-layout once at
    model load, or accept the per-call pack),
  * which policy lever produced it (``lever``), and the scheduler model's
    predicted time (``t_pred``) so callers can log/compare decisions.

Plans carry no arrays: the whole object is static metadata, registered
with :func:`jax.tree_util.register_static` so it crosses jit / scan /
checkpoint boundaries as a leafless pytree and can be closed over or
passed as a static argument without retracing surprises.
"""
from __future__ import annotations

import dataclasses
import math

import jax

from repro.kernels.panel_gemm import EpilogueSpec  # noqa: F401  (re-export)

LEVER_FINE_PANELS = "fine_panels"   # K >= N: occupancy-sized column panels
LEVER_PREPACK = "prepack"           # N > K: deep-K pre-packed weight

# Pack decisions a plan can carry (how execute() treats a RAW weight —
# a PackedWeight operand has already paid its pack at load):
PACK_PREPACKED = "prepacked"   # weight should be packed once at load
PACK_PERCALL = "percall"       # transpose+pad inside the call (baseline)
PACK_NONE = "none"             # no re-layout at all (raw-dot analogue)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Shape-resolved GEMM dispatch decision (see module docstring).

    ``transposed`` records the raw-weight layout execute() will receive
    ([N, K] llama.cpp convention when True); a ``PackedWeight`` operand
    ignores it.  ``sharding_key`` keeps plans for differently-placed
    operands distinct in the cache without holding device objects.

    Fusion fields: ``epilogue`` is the statically-planned
    :class:`~repro.kernels.panel_gemm.EpilogueSpec` the store step
    applies (None = plain GEMM); ``fused_n_splits`` is the horizontal
    split map of a ``pack_fused`` weight (logical part widths — ``n`` is
    then the padded concatenated width).  ``vmem_clamped`` records that
    the policy shrank the requested blocks to honor the kernel VMEM
    budget.

    ``weight_format`` is the pack-time weight format the plan executes
    against: ``"fp32"`` (any raw/packed fp-dtype weight — ``dtype``
    carries the actual operand dtype) or a quantized format from
    ``repro.quant.FORMATS`` (``"int8"`` / ``"ternary"``), in which case
    execute() requires a ``QuantizedPackedWeight`` operand and
    dispatches the backend's dequant-fused run.  Plan-keyed: quantized
    and fp32 plans for one shape are distinct cache entries, and the
    VMEM fit uses the format's bytes-per-element.

    Decode-lane fields: ``decode`` marks a plan resolved by the decode
    policy arm (``gemm.decode_lane()`` scope — skinny block_m, forced
    prepack, split-K considered; plan-keyed so decode and prefill plans
    for one shape never alias).  ``split_k`` is the number of parallel
    K slices the reduction is cut into (1 = the classic kernel); the
    per-slice fp32 partials are combined by the deterministic
    ``splitk_combine`` tree, and the plan's VMEM fit budgets the
    partials slab.  ``split_k`` is resolved per (n, k, format) at the
    canonical decode M — never per operand M — so every decode-bucket
    plan for one weight shares one slice map and ``serve`` stays
    bit-identical to per-request ``generate``.

    Grouped field: ``groups > 0`` marks a plan of the grouped arm — a
    bank of ``groups`` packed weights (``PackedWeight.groups``) applied
    to ``m`` rows sorted into groups whose sizes arrive per call
    (``execute(..., group_sizes=)``): the held experts of a MoE layer.
    Plan-keyed with the groups, K, N and the row bucket ``m``.
    ``in_place`` (grouped plans only) marks a run that reads a stacked
    bank ``[L, groups, K_pad, N_pad]`` in place: execute() folds the
    layer into the group axis instead of slicing the layer's bank out
    (``backends.device_grouped_in_place``: a single TPU's choice).

    Sparse-ternary field: ``density_bucket`` is ``-1`` on the dense arm
    and the pack's zero-group-fraction decile (0..9, see
    ``quant.density_bucket_of``) on a plan resolved for a
    ``SparseTernaryPackedWeight`` — plan-keyed, so the sparse and dense
    ternary arms for one shape never alias in the cache or the plan
    store.  Sparse plans execute the group-granular sparse walk (which
    ignores ``block_k``) and always carry ``split_k=1``.
    """
    m: int
    n: int
    k: int
    dtype: str
    backend: str
    block_m: int
    block_n: int
    block_k: int
    pack: str
    lever: str
    t_pred: float = float("nan")
    occupancy: float = float("nan")
    transposed: bool = False
    sharding_key: str = ""
    validated: bool = False
    epilogue: EpilogueSpec | None = None
    fused_n_splits: tuple = ()
    vmem_clamped: bool = False
    weight_format: str = "fp32"
    split_k: int = 1
    decode: bool = False
    density_bucket: int = -1
    groups: int = 0
    in_place: bool = False

    # ----------------------------------------------------------- geometry
    @property
    def prepack(self) -> bool:
        """True when the policy wants this weight packed at model load."""
        return self.pack == PACK_PREPACKED

    @property
    def m_pad(self) -> int:
        return math.ceil(self.m / self.block_m) * self.block_m

    @property
    def n_pad(self) -> int:
        return math.ceil(self.n / self.block_n) * self.block_n

    @property
    def k_pad(self) -> int:
        return math.ceil(self.k / self.block_k) * self.block_k

    @property
    def grid(self) -> tuple[int, int, int]:
        return (self.m_pad // self.block_m, self.n_pad // self.block_n,
                self.k_pad // self.block_k)

    @property
    def shape(self) -> tuple[int, int, int]:
        return (self.m, self.n, self.k)

    @property
    def glu(self) -> bool:
        return self.epilogue is not None and self.epilogue.glu is not None

    @property
    def quantized(self) -> bool:
        """True when this plan executes a quantized pack-time format."""
        return self.weight_format != "fp32"

    @property
    def sparse(self) -> bool:
        """True when this plan executes the compressed-ternary walk."""
        return self.density_bucket >= 0

    @property
    def n_out(self) -> int:
        """Output column count execute() returns.

        A glu epilogue combines the two column halves of the fused weight
        (output = one logical part); everything else keeps the weight's
        N (fused non-glu output carries every part — ``split_fused``
        slices it by the static split map).
        """
        if self.glu:
            return (self.fused_n_splits[0] if self.fused_n_splits
                    else self.n // 2)
        return self.n

    def describe(self) -> str:
        """One-line human summary (benchmarks / logs)."""
        epi = ""
        if self.epilogue is not None:
            epi = f", epilogue={self.epilogue}"
        if self.fused_n_splits:
            epi += f", fused={self.fused_n_splits}"
        if self.quantized:
            epi += f", weight_format={self.weight_format}"
        if self.sparse:
            epi += f", sparse(bucket={self.density_bucket})"
        if self.decode:
            epi += f", lane=decode, split_k={self.split_k}"
        elif self.split_k != 1:
            epi += f", split_k={self.split_k}"
        if self.vmem_clamped:
            epi += ", vmem_clamped"
        if self.groups:
            epi += f", groups={self.groups}"
        if self.in_place:
            epi += ", in_place"
        return (f"GemmPlan[{self.m}x{self.n}x{self.k} {self.dtype} "
                f"-> {self.backend}, blocks=({self.block_m},{self.block_n},"
                f"{self.block_k}), lever={self.lever}, pack={self.pack}"
                f"{epi}]")
