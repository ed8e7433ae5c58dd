"""Prompt tokens prefilled inside the window over the window's length."""
from chipbench import window


def read(ctx):
    return window.prompt_tok_s(ctx.rec)
