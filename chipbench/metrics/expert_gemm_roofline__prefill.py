"""Roofline time of the held experts' grouped GEMMs in the traced
full-width prefill-step dispatches (``work_mla_moe.expert_gemms``, rows
from the program's counter) over the device time of the ops that compute
them, in %.

Which ops those are: ``ctx.gemm_names["prefill"]`` holds none of them
(its rule looks for a ``dot_general`` op name, and the TPU compiler
names its grouped-matmul kernel itself), so they are found by their
labels in ``ctx.trace.labels``: a ``custom-call`` op whose instruction
name starts with ``ragged-dot`` — XLA's grouped-matmul kernel
(``ragged-dot-none``) and the tile table it computes first
(``ragged-dot-metadata``) — inside the full-width prefill runs.  A
program without them (or without the counter) reads nothing."""
from chipbench import trace_reduce, work_mla_moe


def _is_grouped(label: str) -> bool:
    parts = label.split(" ")
    return (len(parts) >= 2 and parts[0].startswith("ragged-dot")
            and parts[1] == "custom-call")


def read(ctx):
    pairs = ctx.module_runs("prefill")
    if not pairs or ctx.peaks is None or ctx.trace is None:
        return None
    pairs = [(d, r) for d, r in pairs if d.width == ctx.chunk]
    rows = work_mla_moe.prefill_rows(ctx, [d for d, _ in pairs])
    names = {n for n, lab in ctx.trace.labels.items() if _is_grouped(lab)}
    if not pairs or rows is None or not names:
        return None
    op_s = trace_reduce.op_ns_in(ctx.trace, [r for _, r in pairs],
                                 names) / 1e9
    if op_s <= 0:
        return None
    peak, bw = ctx.peaks["peak_flops_bf16"], ctx.peaks["hbm_bw"]
    roof_s = sum(max(f / peak, b / bw)
                 for r in rows
                 for f, b in work_mla_moe.expert_gemms(ctx.cell.config,
                                                       ctx.sizes, r))
    return 100.0 * roof_s / op_s
