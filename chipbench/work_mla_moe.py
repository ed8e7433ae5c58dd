"""Operations and bytes of the DeepSeek-V2 block's prefill steps, counted
from its configuration file (``Cell.config``: the published keys) and the
program's expert-row counter.

* ``model_flops(config, sizes, d, rows)``: the useful work of one prefill
  dispatch ``d`` (a ``window.Dispatch`` over prompt positions
  [start, end)), for utilization (``mla_moe_mfu.prefill``): for each
  real token, the MLA projections (W_q, W_kv_a, W_kv_b once, W_o), its
  causal attention over its own context (a 192-wide score and a 128-wide
  value per head and key), the shared experts, the router and the
  leading dense layers' SwiGLU; the held experts' SwiGLU of the rows the
  step routed to them (``rows`` [MoE layers, held], the program's
  counter); and the LM head of the one row whose logits are used.
* ``expert_gemms(config, sizes, rows)``: one ``(flops, bytes)`` term per
  grouped GEMM of the step (each MoE layer's gate/up and down), for
  ``expert_gemm_roofline.prefill``: flops 2 x rows x K x N over the held
  experts; bytes the held bank once at its stored dtype, the rows in at
  the activation dtype and out as float32 accumulators.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _dims(config: dict, sizes: dict) -> dict:
    return {"d": sizes["d_model"], "h": sizes["num_heads"],
            "qk": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "vd": config["v_head_dim"],
            "r": config["kv_lora_rank"], "dff": sizes["d_ff"],
            "f": config["moe_intermediate_size"],
            "shared": config["n_shared_experts"],
            "router": config["router_experts"],
            "dense": config["first_k_dense_replace"],
            "layers": sizes["num_layers"], "vocab": sizes["vocab_size"]}


def model_flops(config: dict, sizes: dict, d, rows) -> float:
    """Model FLOPs of prefill dispatch ``d`` whose expert rows are
    ``rows`` ([MoE layers, held experts])."""
    c = _dims(config, sizes)
    n = d.end - d.start
    proj = (c["d"] * (c["h"] * c["qk"] + c["r"] + c["rope"])
            + c["r"] * c["h"] * (c["nope"] + c["vd"])
            + c["h"] * c["vd"] * c["d"])
    keys = sum(p + 1 for p in range(d.start, d.end))
    attn = c["h"] * (c["qk"] + c["vd"]) * keys
    moe_layers = c["layers"] - c["dense"]
    per_token_moe = c["d"] * (3 * c["shared"] * c["f"] + c["router"])
    flops = c["layers"] * (n * proj + attn)
    flops += c["dense"] * n * 3 * c["d"] * c["dff"]
    flops += moe_layers * n * per_token_moe
    flops += int(sum(sum(r) for r in rows)) * 3 * c["d"] * c["f"]
    flops += c["d"] * c["vocab"]
    return 2.0 * flops


def expert_gemms(config: dict, sizes: dict, rows) -> list:
    """[(flops, bytes)] of the step's grouped GEMMs, two per MoE layer."""
    c = _dims(config, sizes)
    w = DTYPE_BYTES[sizes["param_dtype"]]
    a = DTYPE_BYTES[sizes["compute_dtype"]]
    out = []
    for layer in rows:
        held, m = len(layer), int(sum(layer))
        for k, n in ((c["d"], 2 * c["f"]), (c["f"], c["d"])):
            out.append((2.0 * m * k * n,
                        held * k * n * w + m * k * a + m * n * 4))
    return out


def prefill_rows(ctx, dispatches) -> list | None:
    """The program's expert rows ([MoE layers, held]) of each of
    ``dispatches`` (prefill ``window.Dispatch``es of the run), or None
    when the program keeps no counter or its dispatches do not pair one
    to one with the run's prefill dispatches.  The n-th prefill dispatch
    the scheduler made is the n-th the run recorded."""
    read = getattr(ctx.stats, "expert_rows_of", None)
    if read is None:
        return None
    rows = read("prefill")
    every = [d for d in ctx.rec.dispatches if d.kind == "prefill"]
    if len(rows) == 0 or len(rows) != len(every):
        return None
    at = {id(d): i for i, d in enumerate(every)}
    if any(id(d) not in at for d in dispatches):
        return None
    return [rows[at[id(d)]].tolist() for d in dispatches]
