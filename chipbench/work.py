"""Operations and bytes of the serving steps, counted from a config's shapes.

Two counts per dispatch:

* ``model_flops``: the useful work, for utilization (``mfu.*``): the
  projections of the real tokens (no chunk padding, no idle slots), the
  causal attention of each real token over its own context, and the LM
  head of each row whose logits are used.
* ``gemms``: one ``(flops, bytes)`` term per projection GEMM as the step
  calls it, at the rows it computes (a prefill chunk computes its padded
  width, a decode step every slot).  Bytes are what the GEMM must move
  at least: its weights once, at the dtype they are stored in, and its
  activations in and out.  A GEMM's roofline time is the larger of its
  flops over the peak rate and its bytes over the HBM bandwidth.

Shapes come as a dict of the program's field names (``spec.Cell.
model_sizes``): num_layers, d_model, num_heads, num_kv_heads, head_dim,
d_ff, vocab_size, param_dtype, compute_dtype.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclasses.dataclass
class Work:
    model_flops: float = 0.0
    gemms: list = dataclasses.field(default_factory=list)  # [(flops, bytes)]

    def __iadd__(self, other: "Work"):
        self.model_flops += other.model_flops
        self.gemms.extend(other.gemms)
        return self

    def gemm_roofline_s(self, peak_flops: float, hbm_bw: float) -> float:
        return sum(max(f / peak_flops, b / hbm_bw) for f, b in self.gemms)


def head_dim(s: dict) -> int:
    return s.get("head_dim") or s["d_model"] // s["num_heads"]


def layer_gemms(s: dict) -> list:
    """(name, K, N) of one layer's projection GEMMs, as the program packs
    them (Q/K/V fused, gate/up fused)."""
    d, hd = s["d_model"], head_dim(s)
    h, hkv = s["num_heads"], s["num_kv_heads"]
    return [("wqkv", d, (h + 2 * hkv) * hd), ("wo", h * hd, d),
            ("w_gate_up", d, 2 * s["d_ff"]), ("w_down", s["d_ff"], d)]


def layer_params(s: dict) -> int:
    return sum(k * n for _, k, n in layer_gemms(s))


def gemm_term(m: int, k: int, n: int, *, w_bytes: int, a_bytes: int,
              o_bytes: int) -> tuple:
    return (2.0 * m * k * n,
            float(k * n * w_bytes + m * k * a_bytes + m * n * o_bytes))


def _gemm_terms(s: dict, rows: int, head_rows: int) -> list:
    w = DTYPE_BYTES[s["param_dtype"]]
    a = DTYPE_BYTES[s["compute_dtype"]]
    out = []
    for _ in range(s["num_layers"]):
        for _, k, n in layer_gemms(s):
            out.append(gemm_term(rows, k, n, w_bytes=w, a_bytes=a,
                                 o_bytes=a))
    out.append(gemm_term(head_rows, s["d_model"], s["vocab_size"],
                         w_bytes=w, a_bytes=a, o_bytes=4))
    return out


def attention_flops(s: dict, keys: int) -> float:
    """QK^T and PV of one query row against ``keys`` keys, all layers."""
    return 4.0 * s["num_heads"] * head_dim(s) * keys * s["num_layers"]


def prefill_chunk(s: dict, width: int, start: int, end: int) -> Work:
    """A chunk of prompt positions [start, end) run at padded ``width``
    rows; the head runs on one row (the prompt's last on a final chunk)."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2
    flops = (2.0 * n * s["num_layers"] * layer_params(s)
             + attention_flops(s, keys)
             + 2.0 * s["d_model"] * s["vocab_size"])
    return Work(flops, _gemm_terms(s, width, 1))


def decode_step(s: dict, slots: int, contexts: list) -> Work:
    """One decode step over ``slots`` rows; ``contexts`` holds, for each
    active slot, the number of keys its new token attends to."""
    a = len(contexts)
    flops = (2.0 * a * s["num_layers"] * layer_params(s)
             + sum(attention_flops(s, c) for c in contexts)
             + 2.0 * a * s["d_model"] * s["vocab_size"])
    return Work(flops, _gemm_terms(s, slots, slots))
