"""Three-term roofline from the compiled dry-run artifact.

No wall clock exists for the target (TPU v5e) on this CPU host, so the
§Roofline deliverable is derived statically, per (arch × shape × mesh):

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = Σ wire_bytes(op) / link_bw   over collective ops

``compiled.cost_analysis()`` reports the PER-DEVICE partitioned module
(calibrated: a (256,512)x(512,1024) matmul over 8 devices reports
global/8 flops), so no division by chip count is applied to the first two
terms.  Collective ops are parsed from the compiled (post-GSPMD) HLO —
the pre-partitioning StableHLO has none — with wire bytes per device
derived from the result shape and replica group size under the standard
ring algorithms:

  all-gather      R·(s-1)/s      (R = result bytes, s = group size)
  all-reduce      2·R·(s-1)/s
  reduce-scatter  R·(s-1)
  all-to-all      R·(s-1)/s
  collective-permute  R

The single-link-bandwidth model (~50 GB/s ICI per the brief) treats every
group as ring-connected; cross-pod (DCN) groups are charged at
``dcn_bw`` when the group telescopes over the pod axis (group size == the
pod count on the multi-pod mesh) — recorded per-op so EXPERIMENTS.md can
show the DCN share.
"""
from __future__ import annotations

import dataclasses
import re

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture
# table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect (four ICI links, 50 GB/s
# each).  No fp32 peak is published: the MXU multiplies bf16, so the
# bf16 figure is the compute floor for every operand dtype, fp32 GEMMs
# included.  A kind missing here has no roofline: callers report "not
# measured" rather than borrow another device's peaks.
PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "peak_flops_bf16": 197e12,
        "peak_ops_int8": 393e12,
        "hbm_bw": 819e9,                # bytes/s per chip
        "hbm_bytes": 16 * 2**30,
        "ici_bw": 50e9,                 # bytes/s per link
        "source": "Google Cloud documentation, TPU v5e",
    },
}
V5E = PEAKS["TPU v5 lite"]


def device_peaks(device_kind: str | None = None) -> dict | None:
    """Peaks of ``device_kind`` (default: the first device JAX sees);
    None when the table has no entry for it — the CPU among them."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return PEAKS.get(device_kind)


DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?P<shape>\([^)]*\)|[\w\[\],{}:]+)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(",
    re.M)
_SHAPE_RE = re.compile(r"(?P<dt>\w+)\[(?P<dims>[\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    wire_bytes: float      # per device, ring model
    line: str = ""


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group("dt")
        if dt not in DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def _wire_bytes(kind: str, result_bytes: int, s: int) -> float:
    if s <= 1:
        return 0.0
    if kind == "all-gather":
        return result_bytes * (s - 1) / s
    if kind == "all-reduce":
        return 2.0 * result_bytes * (s - 1) / s
    if kind == "reduce-scatter":
        return float(result_bytes) * (s - 1)
    if kind == "all-to-all":
        return result_bytes * (s - 1) / s
    return float(result_bytes)           # collective-permute


def parse_collectives(hlo_text: str, total_devices: int,
                      ) -> list[CollectiveOp]:
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        if "-done" in line.split("=", 1)[0]:
            continue                       # async pair: count start only
        kind = m.group("op")
        rb = _shape_bytes(m.group("shape"))
        s = _group_size(line, total_devices)
        out.append(CollectiveOp(kind, rb, s, _wire_bytes(kind, rb, s),
                                line.strip()[:180]))
    return out


def collective_seconds(ops: list[CollectiveOp], *, link_bw: float,
                       dcn_bw: float | None = None,
                       dcn_group_size: int | None = None) -> dict:
    """Total collective seconds + per-kind/per-fabric breakdown."""
    total = 0.0
    by_kind: dict[str, float] = {}
    dcn_s = 0.0
    for op in ops:
        bw = link_bw
        is_dcn = (dcn_group_size is not None
                  and op.group_size == dcn_group_size)
        if is_dcn and dcn_bw:
            bw = dcn_bw
        t = op.wire_bytes / bw
        total += t
        by_kind[op.kind] = by_kind.get(op.kind, 0.0) + t
        if is_dcn:
            dcn_s += t
    return {"seconds": total, "by_kind": by_kind, "dcn_seconds": dcn_s,
            "num_ops": len(ops),
            "wire_bytes": sum(op.wire_bytes for op in ops)}


def model_flops(cfg, shape) -> float:
    """Useful FLOPs: 6·N_active·tokens (train) / 2·N_active·tokens
    (prefill) / 2·N_active·new_tokens (decode)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 token


# Weight bytes per element by pack-time format (the quantized formats
# stream codes, not floats): fp32 full precision, int8 one byte,
# ternary 2-bit codes packed 4-per-byte.
_FORMAT_BYTES = {"fp32": 4.0, "int8": 1.0, "ternary": 0.25}


def gemm_roofline(m: int, n: int, k: int, *, hw: dict,
                  weight_format: str = "fp32", act_bytes: int = 4,
                  weight_density: float = 1.0) -> float:
    """Analytic lower-bound seconds for ONE ``[m,k] @ [k,n]`` dispatch —
    the denominator of the flight recorder's ``roofline_frac``.

    ``hw`` is one :data:`PEAKS` entry (:func:`device_peaks` of the
    device that ran the dispatch).  Two terms, take the max: compute
    (``2mnk`` over the bf16 peak — the MXU floor whatever the operand
    dtype, so an fp32 GEMM's bound is optimistic) and memory (the
    operand/result traffic floor: activations + result at ``act_bytes``,
    weights at the pack format's bytes-per-element — the term decode's
    skinny-M dispatches live on, and why quantized decode beats fp32 at
    the same FLOPs).  Single-dispatch and collective-free by
    construction; the step-level three-term model stays
    :func:`roofline_terms`.

    ``weight_density`` is the occupied-group fraction of a sparse-
    ternary pack (``SparseTernaryPackedWeight.density``; 1.0 = dense):
    the compressed layout stores — and the sparse walk streams and
    multiplies — only the occupied K-groups, so both the weight-byte
    term and the FLOP term scale by it.  That makes ``roofline_frac``
    honest for sparse dispatches: measured against the work the layout
    actually implies, not the dense shape's."""
    flops = 2.0 * m * n * k * weight_density
    wb = _FORMAT_BYTES.get(weight_format, 4.0)
    bytes_moved = ((m * k + m * n) * act_bytes
                   + k * n * wb * weight_density)
    t_compute = flops / hw["peak_flops_bf16"]
    t_memory = bytes_moved / hw["hbm_bw"]
    return max(t_compute, t_memory)


def roofline_terms(*, flops_per_device: float, bytes_per_device: float,
                   collective: dict, chips: int, model_fl: float,
                   hw: dict) -> dict:
    # bf16 is the MXU's multiply width: fp32 takes the same floor
    peak = hw["peak_flops_bf16"]
    t_c = flops_per_device / peak
    t_m = bytes_per_device / hw["hbm_bw"]
    t_x = collective["seconds"]
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dominant = max(terms, key=terms.get)
    bound = max(t_c, t_m, t_x)
    total_hlo_flops = flops_per_device * chips
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "bound_s": bound,
        # fraction of roofline achieved if the dominant term were the
        # whole step (higher = closer to the compute roofline)
        "roofline_fraction": (t_c / bound) if bound > 0 else 0.0,
        "hlo_flops_per_device": flops_per_device,
        "hlo_flops_global": total_hlo_flops,
        "model_flops": model_fl,
        "useful_ratio": model_fl / total_hlo_flops if total_hlo_flops
        else 0.0,
        "mfu_upper_bound": (model_fl / (chips * peak)) / bound
        if bound > 0 else 0.0,
        "chips": chips,
    }
