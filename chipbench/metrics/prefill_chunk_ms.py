"""Mean fenced time (ms) of the full-width prefill-chunk dispatches
inside the window (``ServeStats.prefill_tick_ms``)."""


def read(ctx):
    ms = [t for t, full in zip(ctx.prefill_ticks, ctx.full_width) if full]
    return sum(ms) / len(ms) if ms else None
