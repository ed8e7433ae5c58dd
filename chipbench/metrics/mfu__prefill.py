"""Model FLOPs of the traced prefill-step dispatches (``work.py``) over
their device program time times the bf16 peak, in %."""
from chipbench.metrics import _device


def read(ctx):
    return _device.mfu(ctx, "prefill")
