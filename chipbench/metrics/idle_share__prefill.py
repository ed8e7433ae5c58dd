"""Share (%) of the traced window in which no op ran on the device."""
from chipbench.metrics import _device


def read(ctx):
    return _device.idle_share(ctx)
