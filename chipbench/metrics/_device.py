"""Arithmetic shared by the readers of device-trace metrics."""
from chipbench import trace_reduce, work


def mfu(ctx, kind):
    """Model FLOPs of every traced ``kind`` dispatch over the device time
    of their step programs times the bf16 peak, in %."""
    pairs = ctx.module_runs(kind)
    if not pairs or ctx.peaks is None:
        return None
    busy_s = sum(b - a for _, (_, a, b) in pairs) / 1e9
    flops = sum(ctx.work_of(d).model_flops for d, _ in pairs)
    return 100.0 * flops / (busy_s * ctx.peaks["peak_flops_bf16"])


def gemm_roofline(ctx, kind):
    """Roofline time of the projection GEMMs over the device time of the
    ops that compute them, in %, over the traced dispatches of the step
    program whose HLO named those ops (a prefill's full-width chunk; the
    narrower chunk tails are other programs)."""
    pairs = ctx.module_runs(kind)
    names = ctx.gemm_names.get(kind)
    if not pairs or not names or ctx.peaks is None:
        return None
    if kind == "prefill":
        pairs = [(d, r) for d, r in pairs if d.width == ctx.chunk]
    if not pairs:
        return None
    op_s = trace_reduce.op_ns_in(ctx.trace, [r for _, r in pairs],
                                 names) / 1e9
    if op_s <= 0:
        return None
    total = work.Work()
    for d, _ in pairs:
        total += ctx.work_of(d)
    roof_s = total.gemm_roofline_s(ctx.peaks["peak_flops_bf16"],
                                   ctx.peaks["hbm_bw"])
    return 100.0 * roof_s / op_s


def idle_share(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(ctx.trace)
