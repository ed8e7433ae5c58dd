"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A device missing here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s per chip.
Copied from the program's ``roofline.analysis.PEAKS`` so that no change
to the program can move the yardstick.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "peak_flops_bf16": 197e12,
        "peak_ops_int8": 393e12,
        "hbm_bw": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
