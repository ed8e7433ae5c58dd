"""Output tokens emitted inside the window over the window's length."""
from chipbench import window


def read(ctx):
    return window.out_tok_s(ctx.rec)
