"""Roofline: 3-term analysis from compiled dry-run artifacts."""
from repro.roofline.analysis import (
    PEAKS, V5E, CollectiveOp, collective_seconds, device_peaks,
    gemm_roofline, model_flops, parse_collectives, roofline_terms,
)

__all__ = ["PEAKS", "V5E", "CollectiveOp", "collective_seconds",
           "device_peaks", "gemm_roofline", "model_flops",
           "parse_collectives", "roofline_terms"]
