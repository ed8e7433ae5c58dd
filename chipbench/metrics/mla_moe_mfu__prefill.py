"""Model FLOPs of the traced prefill-step dispatches of a DeepSeek-V2
block (``work_mla_moe.model_flops``, expert rows from the program's
counter) over their device program time times the bf16 peak, in %."""
from chipbench import work_mla_moe


def read(ctx):
    pairs = ctx.module_runs("prefill")
    if not pairs or ctx.peaks is None:
        return None
    rows = work_mla_moe.prefill_rows(ctx, [d for d, _ in pairs])
    if rows is None:
        return None
    busy_s = sum(b - a for _, (_, a, b) in pairs) / 1e9
    flops = sum(work_mla_moe.model_flops(ctx.cell.config, ctx.sizes, d, r)
                for (d, _), r in zip(pairs, rows))
    return 100.0 * flops / (busy_s * ctx.peaks["peak_flops_bf16"])
