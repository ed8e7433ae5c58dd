"""Mean host time (ms) per scheduler step in the traced window: each
``step()``'s wall time less the time its fences waited on the device
(``ServeStats.steps``, one ``StepRecord`` per step), over the steps that
started while the profiler ran (the window's last ``bench.TRACE_S``
seconds, or all of a shorter window).  These are the program's ``sched_step`` spans less their
``fence`` spans; a program without step records reads nothing."""
from chipbench import bench


def read(ctx):
    steps = getattr(ctx.stats, "steps", None)
    if not steps or ctx.rec.w1 is None:
        return None
    lo, hi = max(ctx.rec.w0, ctx.rec.w1 - bench.TRACE_S), ctx.rec.w1
    host = [r.wall_ms - r.fence_ms for r in steps if lo <= r.t0 < hi]
    return sum(host) / len(host) if host else None
