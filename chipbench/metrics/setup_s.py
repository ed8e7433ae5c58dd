"""Set-up time (s): process start to the start of the window, on the host
clock: load and pack, warm-up compiles, and the ramp of the traffic."""


def read(ctx):
    return ctx.setup_s
