"""The quantization error ledger — Table-4 discipline for reduced
precision.

The paper never ships a configuration whose numerics it has not
measured: its Table 4 reports BNNS Graph's per-shape max-abs error (up
to 1.4e-3) instead of hand-waving "close enough".  This ledger applies
the same discipline to our own quantized formats: every concrete
quantized pack is probed against its fp32 oracle, the per-(shape,
format) error is RECORDED, and the per-format tolerance is ENFORCED at
pack time — reduced precision cannot drift silently.

Schema (one entry per ``(n, k, fmt)``):

  * ``max_abs``  — max |y_quant - y_fp32| over the probe GEMM output
    (fp32 oracle: ``x @ w`` on the original weights).
  * ``max_rel``  — ``max_abs / max |y_fp32|``: output-normalized
    relative error.  Normalizing by the output's own magnitude (not
    elementwise) keeps near-zero outputs from exploding the metric —
    documented in docs/quantization.md.
  * ``tol``      — the format's declared ``max_rel`` tolerance.

``gemm.validate_plan`` consults the ledger for quantized plans: a plan
whose ledger entry exceeds its tolerance is rejected, mirroring the
autotune bit-exactness reject protocol for fp32 plans.
"""
from __future__ import annotations

import dataclasses
import threading

import jax.numpy as jnp
import numpy as np

# Per-format max_rel tolerances (the contract docs/quantization.md
# states).  int8 per-channel symmetric lands around 1e-3..9e-3 on
# gaussian weights (quant step = max|w|/127); ternary is a 2-bit format
# — its reconstruction error is O(0.5 sigma_w) per weight, so the
# output-normalized error sits near 0.4-0.5 on the paper shapes.  0.75
# is the enforced ceiling; anything above it means the quantizer (not
# the format) broke.
TOLERANCES = {"int8": 1e-2, "ternary": 0.75}

# Probe row count: enough rows that the max statistics are stable, small
# enough that packing a whole model stays cheap at load.
PROBE_M = 64


class QuantToleranceError(RuntimeError):
    """A quantized pack's measured error exceeded its format tolerance."""


@dataclasses.dataclass(frozen=True)
class LedgerEntry:
    n: int
    k: int
    fmt: str
    max_abs: float
    max_rel: float
    tol: float
    probe_m: int
    # occupied K-group fraction of the probed pack: 1.0 for every dense
    # pack; < 1.0 when a ternary pack crossed to the compressed
    # zero-group layout (SparseTernaryPackedWeight.density)
    density: float = 1.0

    @property
    def within_tol(self) -> bool:
        return self.max_rel <= self.tol

    @property
    def sparse(self) -> bool:
        return self.density < 1.0

    def row(self) -> dict:
        """Report row: the entry's shape, format and error columns."""
        return {"N": self.n, "K": self.k, "format": self.fmt,
                "max_abs_err": self.max_abs, "max_rel_err": self.max_rel,
                "tolerance": self.tol, "within_tol": self.within_tol,
                "density": round(self.density, 4)}


_entries: dict[tuple[int, int, str], LedgerEntry] = {}
_lock = threading.Lock()


def tolerance(fmt: str) -> float:
    try:
        return TOLERANCES[fmt]
    except KeyError:
        raise KeyError(f"no tolerance declared for format {fmt!r}; "
                       f"known: {sorted(TOLERANCES)}") from None


def record(entry: LedgerEntry) -> LedgerEntry:
    with _lock:
        _entries[(entry.n, entry.k, entry.fmt)] = entry
    return entry


def lookup(n: int, k: int, fmt: str) -> LedgerEntry | None:
    with _lock:
        return _entries.get((int(n), int(k), fmt))


def entries() -> list[LedgerEntry]:
    with _lock:
        return sorted(_entries.values(), key=lambda e: (e.fmt, e.k, e.n))


def clear() -> None:
    with _lock:
        _entries.clear()


def measure(w_fp32, qpw, *, enforce: bool = True,
            probe_m: int = PROBE_M) -> LedgerEntry:
    """Probe one quantized pack against its fp32 oracle and record the
    entry (pack-time enforcement path).

    The probe is a deterministic gaussian ``x [probe_m, K]`` seeded by
    the shape, the oracle is the plain fp32 ``x @ w``, and the quantized
    side multiplies the SAME x against the dequantized panels — the
    error measured is purely the format's, not the kernel's (the kernel
    vs dequant-oracle contract is the separate bit-exact gate in
    ``quant/kernels``).  A stacked ``[L, K, N]`` pack is probed per
    layer and the WORST layer's errors become the shape's entry, so
    scan-over-layers serving weights are gated exactly like 2-D packs.
    """
    from repro.quant import formats as F
    w = jnp.asarray(w_fp32, jnp.float32)
    k, n = int(w.shape[-2]), int(w.shape[-1])
    rng = np.random.default_rng((k * 1_000_003 + n) % (2**31))
    x = jnp.asarray(rng.standard_normal((probe_m, k)), jnp.float32)
    w3 = w.reshape((-1, k, n))
    deq3 = F.dequantize(qpw)[..., :k, :n].reshape((-1, k, n))
    max_abs = max_rel = 0.0
    for wl, dl in zip(w3, deq3):
        y_ref = np.asarray(jnp.dot(x, wl,
                                   preferred_element_type=jnp.float32))
        y_q = np.asarray(jnp.dot(x, dl,
                                 preferred_element_type=jnp.float32))
        abs_l = float(np.max(np.abs(y_q - y_ref))) if y_ref.size else 0.0
        denom = float(np.max(np.abs(y_ref))) if y_ref.size else 0.0
        rel_l = abs_l / max(denom, 1e-30)
        if rel_l >= max_rel:
            max_abs, max_rel = abs_l, rel_l
    entry = record(LedgerEntry(n=int(qpw.n), k=int(qpw.k), fmt=qpw.fmt,
                               max_abs=max_abs, max_rel=max_rel,
                               tol=tolerance(qpw.fmt), probe_m=probe_m,
                               density=float(getattr(qpw, "density",
                                                     1.0))))
    if enforce and not entry.within_tol:
        raise QuantToleranceError(
            f"quantized pack [{qpw.k}x{qpw.n}] fmt={qpw.fmt}: max_rel "
            f"error {max_rel:.3e} exceeds the {qpw.fmt} tolerance "
            f"{entry.tol:.1e} (error ledger enforcement; see "
            f"docs/quantization.md)")
    return entry
