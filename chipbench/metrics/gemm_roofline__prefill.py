"""Roofline time of the projection GEMMs of the traced prefill-step
dispatches (``work.py``) over the device time of the ops that compute
them (``trace_reduce.gemm_op_names``), in %."""
from chipbench.metrics import _device


def read(ctx):
    return _device.gemm_roofline(ctx, "prefill")
