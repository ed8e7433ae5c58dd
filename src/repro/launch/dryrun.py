# XLA device-count pin: MUST precede every other import (jax locks the
# device count at first init).  512 host devices = 2 pods x 256 chips.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))
# compile-only analysis targets the TPU MXU: native-dtype dot operands
# (the CPU thunk runtime can't EXECUTE bf16 dots, but never executes here)
os.environ.setdefault("REPRO_MXU_DOTS", "1")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves, without hardware: (a) the sharding config is coherent (GSPMD
partitions every step over 256- and 512-chip meshes), (b) the memory plan
fits (memory_analysis), and (c) the cost/collective profile that feeds
§Roofline (cost_analysis + compiled-HLO collective parse).

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all --mesh both      # every cell
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import gemm as gemm_api
from repro.configs.base import SHAPES, TrainConfig
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo, transformer
from repro.parallel import sharding as Sh
from repro.roofline import analysis as R
from repro.roofline.hlo_cost import HloCostModel
from repro.runtime import train_loop

# the dry-run models a v5e deployment (compile-only, no chip attached)
HW = R.V5E


def _lower_cell(cfg, shape, mesh, *, packed: bool = True,
                microbatch_per_device: int = 1,
                train_overrides: dict | None = None):
    """Build + lower the step this cell exercises.  Returns (lowered,
    extras dict)."""
    if cfg.family == "moe" and cfg.moe_dispatch_groups == 0 \
            and cfg.moe_dispatch == "grouped":
        shards = Sh.axis_size(mesh, ("pod", "data"))
        if shape.global_batch % shards == 0:
            cfg = dataclasses.replace(cfg, moe_dispatch_groups=shards)
    if shape.kind == "train":
        tc = TrainConfig(microbatch_per_device=microbatch_per_device,
                         **(train_overrides or {}))
        step = train_loop.make_train_step(
            cfg, tc, mesh,
            batch_shardings=train_loop.batch_shardings(cfg, shape, mesh))
        state = train_loop.abstract_state(cfg, tc)
        batch = model_zoo.input_specs(cfg, shape)
        return step.lower(state, batch), {"step": "train_step"}

    raw = model_zoo.abstract_params(cfg)
    if packed:
        params = jax.eval_shape(
            lambda p: model_zoo.pack_for_inference(cfg, p), raw)
    else:
        params = raw
    # serving placement: TP-only (data-replicated) when weights fit —
    # §Perf iteration C1 (see parallel/sharding.serve_param_specs)
    p_sh = Sh.serve_param_shardings(params, mesh)
    shard_fn = Sh.activation_sharder(mesh)
    ins = model_zoo.input_specs(cfg, shape)

    if shape.kind == "prefill":
        def prefill_fn(params, inputs):
            return transformer.prefill(cfg, params, inputs,
                                       max_len=shape.seq_len,
                                       shard_fn=shard_fn)
        i_sh = jax.NamedSharding(
            mesh, Sh.batch_spec(shape.global_batch, mesh,
                                extra_dims=ins["inputs"].ndim - 1))
        fn = jax.jit(prefill_fn, in_shardings=(p_sh, i_sh))
        return fn.lower(params, ins["inputs"]), {"step": "prefill"}

    # decode: one new token against a seq_len-deep cache
    def decode_fn(params, cache, tokens):
        return transformer.decode_step(cfg, params, cache, tokens,
                                       shard_fn=shard_fn)
    c_sh = Sh.cache_shardings(ins["cache"], mesh)
    t_sh = jax.NamedSharding(
        mesh, Sh.batch_spec(shape.global_batch, mesh,
                            extra_dims=ins["tokens"].ndim - 1))
    fn = jax.jit(decode_fn, in_shardings=(p_sh, c_sh, t_sh),
                 out_shardings=(None, c_sh))
    return fn.lower(params, ins["cache"], ins["tokens"]), \
        {"step": "serve_step"}


def _attn_ideal_bytes(cfg, shape, chips: int) -> float:
    """Per-device HBM traffic of the DEPLOYED attention path — the Pallas
    flash kernel (kernels/flash_attention.py), which keeps score blocks
    in VMEM and touches HBM only for q/k/v/out (+grads in training).

    The XLA-CPU lowering of the jnp fallback materializes every score
    block (measured: the dominant memory term on SSM/hybrid train cells),
    so §Roofline reports both the XLA-path term and this kernel-adjusted
    term.  Model: bytes(q+k+v+out) × passes, where passes ≈ 2 (fwd r+w)
    for inference and 6 for training (fwd + bwd recompute + grad IO),
    × layers, global traffic ÷ chips.
    """
    if cfg.attention_kind == "none":
        return 0.0
    b = shape.global_batch
    s = shape.seq_len if shape.kind != "decode" else 1
    t = shape.seq_len
    if cfg.window is not None and cfg.local_global_period == 0:
        t = min(t, cfg.window)
    if cfg.attention_kind == "mla":
        hq, dq_ = cfg.num_heads, cfg.kv_lora_rank + cfg.qk_rope_dim
        hkv, dk_ = 1, cfg.kv_lora_rank + cfg.qk_rope_dim
    else:
        hq, dq_ = cfg.num_heads, cfg.head_dim
        hkv, dk_ = cfg.num_kv_heads, cfg.head_dim
    per_layer = 4.0 * (b * s * hq * dq_ * 2        # q + out
                       + b * t * hkv * dk_ * 2)    # k + v
    passes = 6.0 if shape.kind == "train" else 2.0
    return per_layer * passes * cfg.num_layers / chips


def _ssd_ideal_bytes(cfg, shape, chips: int) -> float:
    """Per-device HBM traffic of the Pallas SSD kernel (kernels/ssd.py):
    x/a/b/c read + y written once per pass; quadratic intra-chunk blocks
    stay in VMEM.  passes ≈ 2 inference / 6 training (see
    _attn_ideal_bytes)."""
    if not cfg.ssm_heads:
        return 0.0
    b = shape.global_batch
    t = shape.seq_len if shape.kind != "decode" else 1
    h, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    per_layer = 4.0 * (b * t * h * p * 2        # x + y
                       + b * t * h              # a
                       + b * t * g * n * 2)     # b + c
    passes = 6.0 if shape.kind == "train" else 2.0
    return per_layer * passes * cfg.num_layers / chips


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             packed: bool = True, verbose: bool = True,
             microbatch_per_device: int = 1,
             train_overrides: dict | None = None,
             gemm_backend: str | None = None) -> dict:
    cfg = model_zoo.get_config(arch)
    shape = SHAPES[shape_name]
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size

    t0 = time.perf_counter()
    # the use_backend scope covers tracing/lowering, so every gemm plan in
    # the cell resolves to the requested backend (default: xla — Pallas
    # can't lower on the forced-host platform this dry-run pins)
    with gemm_api.use_backend(gemm_backend):
        lowered, extras = _lower_cell(
            cfg, shape, mesh, packed=packed,
            microbatch_per_device=microbatch_per_device,
            train_overrides=train_overrides)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()

    # Loop-aware walker (XLA's cost_analysis counts scan bodies once —
    # see roofline/hlo_cost.py); numbers below are per device.
    model = HloCostModel(hlo, total_devices=chips,
                         dcn_group_size=(2 if multi else None),
                         tags={"attn": r"flash_attn", "ssd": r"ssd_chunk"})
    cost = model.cost()
    ici_wire = cost.coll_wire_bytes - cost.coll_wire_dcn
    dcn_bw = HW["ici_bw"] / 2
    coll = {
        "seconds": ici_wire / HW["ici_bw"] + cost.coll_wire_dcn / dcn_bw,
        "dcn_seconds": cost.coll_wire_dcn / dcn_bw,
        "by_kind": {k: v / HW["ici_bw"]
                    for k, v in cost.coll_by_kind.items()},
        "num_ops": sum(1 for c in model.comps.values() for o in c.ops
                       if o.op in ("all-gather", "all-reduce",
                                   "reduce-scatter", "all-to-all",
                                   "collective-permute")),
        "wire_bytes": cost.coll_wire_bytes,
    }

    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
    }
    mem["peak_bytes_per_device"] = (mem["argument_bytes"]
                                    + mem["output_bytes"]
                                    + mem["temp_bytes"]
                                    - mem["alias_bytes"])
    terms = R.roofline_terms(
        flops_per_device=cost.flops,
        bytes_per_device=cost.hbm_bytes,
        collective=coll, chips=chips,
        model_fl=R.model_flops(cfg, shape), hw=HW)
    # Pallas-kernel-adjusted memory term: replace the XLA-materialized
    # attention / SSD block traffic (tagged via named_scope) with the
    # kernels' analytic HBM traffic (kernels/flash_attention.py,
    # kernels/ssd.py keep those blocks in VMEM).
    attn_xla = cost.hbm_by_tag.get("attn", 0.0)
    ssd_xla = cost.hbm_by_tag.get("ssd", 0.0)
    attn_ideal = _attn_ideal_bytes(cfg, shape, chips)
    ssd_ideal = _ssd_ideal_bytes(cfg, shape, chips)
    adj_bytes = max(cost.hbm_bytes - attn_xla - ssd_xla, 0.0) \
        + min(attn_ideal, attn_xla) + min(ssd_ideal, ssd_xla)
    terms["memory_attn_xla_s"] = attn_xla / HW["hbm_bw"]
    terms["memory_ssd_xla_s"] = ssd_xla / HW["hbm_bw"]
    terms["memory_adjusted_s"] = adj_bytes / HW["hbm_bw"]
    adj_terms = {"compute": terms["compute_s"],
                 "memory": terms["memory_adjusted_s"],
                 "collective": terms["collective_s"]}
    terms["dominant_adjusted"] = max(adj_terms, key=adj_terms.get)
    terms["bound_adjusted_s"] = max(adj_terms.values())

    by_kind_bytes = dict(cost.coll_by_kind)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": chips, "step": extras["step"], "packed": packed,
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost": {"flops_per_device": cost.flops,
                 "hbm_bytes_per_device": cost.hbm_bytes,
                 "loops": model.loops,
                 "xla_single_count_flops": float(ca.get("flops", 0.0)),
                 "xla_single_count_bytes": float(
                     ca.get("bytes accessed", 0.0))},
        "collectives": {"num_ops": coll["num_ops"],
                        "wire_bytes_per_device": coll["wire_bytes"],
                        "seconds": coll["seconds"],
                        "dcn_seconds": coll["dcn_seconds"],
                        "by_kind_s": coll["by_kind"],
                        "by_kind_bytes": by_kind_bytes},
        "roofline": terms,
        "fits_hbm": mem["peak_bytes_per_device"] <= HW["hbm_bytes"],
    }
    if verbose:
        print(f"== {arch} × {shape_name} × {mesh_name} "
              f"({extras['step']}, {chips} chips) ==")
        print("memory_analysis:", ma)
        print("cost_analysis (xla, single-count):",
              {k: v for k, v in ca.items() if "utilization" not in k
               and "bytes accessed" not in k or k == "bytes accessed"})
        print(f"cost walker: {cost.flops/1e12:.3f} TFLOP/device, "
              f"{cost.hbm_bytes/1e9:.2f} GB HBM/device, "
              f"loops={[(l['trips']) for l in model.loops]}")
        print(f"collectives: {coll['num_ops']} ops, "
              f"{coll['wire_bytes']/1e6:.1f} MB/device on the wire")
        print(f"roofline: compute {terms['compute_s']*1e3:.3f} ms | "
              f"memory {terms['memory_s']*1e3:.3f} ms "
              f"(pallas-adj {terms['memory_adjusted_s']*1e3:.3f}) | "
              f"collective {terms['collective_s']*1e3:.3f} ms "
              f"→ {terms['dominant']}-bound "
              f"(adj: {terms['dominant_adjusted']}); useful-FLOP ratio "
              f"{terms['useful_ratio']:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    choices=model_zoo.list_archs() + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every non-skipped (arch × shape) cell")
    ap.add_argument("--raw", action="store_true",
                    help="serve steps with unpacked weights (baseline)")
    ap.add_argument("--gemm-backend", default=None,
                    choices=gemm_api.list_backends(),
                    help="GEMM backend the cells plan against")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s) for a, s, _skip in model_zoo.cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        for mesh_name in meshes:
            tag = f"{arch}__{shape_name}__{mesh_name}" \
                  + ("" if not args.raw else "__raw")
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        continue
            try:
                rec = run_cell(arch, shape_name, mesh_name,
                               packed=not args.raw,
                               microbatch_per_device=args.microbatch,
                               gemm_backend=args.gemm_backend)
            except Exception as e:                      # noqa: BLE001
                failures += 1
                rec = {"arch": arch, "shape": shape_name,
                       "mesh": mesh_name, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"FAIL {tag}: {rec['error']}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            jax.clear_caches()          # bound compile-cache memory
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
