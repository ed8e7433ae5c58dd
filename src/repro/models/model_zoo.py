"""Model zoo: arch registry, reduced smoke configs, input specs, and the
load-time weight pack (paper lever 2 applied to a whole model).

``input_specs`` returns ShapeDtypeStruct stand-ins only — the full-scale
configs are never allocated on this host; they exist solely to be lowered
+ compiled in launch/dryrun.py.  ``[audio]``/``[vlm]`` archs get stub
frontends per the assignment: precomputed frame/patch embeddings
[B, S, d_model] instead of token ids.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from repro import gemm as gemm_api
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.core import packing
from repro.models import transformer

ARCHS = {
    "musicgen-medium": "musicgen_medium",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "deepseek-7b": "deepseek_7b",
    "h2o-danube-3-4b": "h2o_danube3_4b",
    "gemma2-9b": "gemma2_9b",
    "stablelm-3b": "stablelm_3b",
    "internvl2-76b": "internvl2_76b",
    "mamba2-370m": "mamba2_370m",
    "hymba-1.5b": "hymba_1_5b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "deepseek-v2-lite-ep8": "deepseek_v2_lite_ep8",
}

# The paper's Table 3: twelve LLM prefill GEMMs at M = S = 128, as
# (model, op, N, K).  These drive the GEMM tests and the autotune sweep.
PAPER_GEMM_SHAPES = [
    ("gpt2-style", "qkv", 2048, 2048),
    ("gpt2-style", "ffn1", 8192, 2048),
    ("gpt2-style", "ffn2", 2048, 8192),
    ("gpt2-style", "lm_head", 60000, 2048),
    ("tinyllama-1.1b", "qkv", 2048, 2048),
    ("tinyllama-1.1b", "ffn1", 5632, 2048),
    ("tinyllama-1.1b", "ffn2", 2048, 5632),
    ("tinyllama-1.1b", "lm_head", 32000, 2048),
    ("llama-7b", "qkv", 4096, 4096),
    ("llama-7b", "ffn1", 11008, 4096),
    ("llama-7b", "ffn2", 4096, 11008),
    ("llama-7b", "lm_head", 32000, 4096),
]
PAPER_M = 128

# long_500k applicability (DESIGN.md §6): sub-quadratic decode state only.
LONG_CONTEXT_ARCHS = {"mamba2-370m", "hymba-1.5b", "h2o-danube-3-4b"}


def list_archs() -> list[str]:
    return list(ARCHS)


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; choose from {list(ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{ARCHS[name]}")
    return mod.CONFIG


def cells(include_skipped: bool = False):
    """All (arch, shape) assignment cells; skips carry a reason."""
    out = []
    for arch in ARCHS:
        for sname, shape in SHAPES.items():
            skip = None
            if sname == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                skip = ("full-attention arch: 524k-context decode cache / "
                        "quadratic prefill out of serving budget "
                        "(DESIGN.md §6)")
            if skip is None or include_skipped:
                out.append((arch, sname, skip))
    return out


# ----------------------------------------------------------- reduced configs
def reduced_config(cfg: ModelConfig) -> ModelConfig:
    """Family-preserving smoke config: tiny widths, same structure."""
    kw: dict = dict(
        name=cfg.name + "-smoke", num_layers=2, d_model=64,
        vocab_size=128, remat=False,
    )
    if cfg.attention_kind in ("gqa", "parallel_ssm"):
        kw.update(num_heads=4, num_kv_heads=max(1, min(cfg.num_kv_heads, 2)),
                  head_dim=16)
    if cfg.attention_kind == "mla":
        kw.update(num_heads=4, num_kv_heads=4, head_dim=24,
                  q_lora_rank=32 if cfg.q_lora_rank else 0,
                  kv_lora_rank=16, qk_rope_dim=8, qk_nope_dim=16,
                  v_head_dim=16)
    if cfg.yarn_factor:
        kw.update(yarn_original_max_pos=64)
    if cfg.d_ff:
        kw.update(d_ff=128)
    if cfg.family == "moe":
        kw.update(num_experts=8, experts_per_token=2, moe_d_ff=64)
        if cfg.held_experts:           # a share: half of the 8 experts
            kw.update(held_experts=(0, 4))
    if cfg.first_k_dense:
        kw.update(num_layers=3, first_k_dense=1)
    if cfg.ssm_heads:
        kw.update(ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=16)
    if cfg.window is not None:
        kw.update(window=32)
    return dataclasses.replace(cfg, **kw)


# ------------------------------------------------------------- input specs
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig | str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the step the
    shape exercises (train_step / prefill / decode serve_step)."""
    if isinstance(shape, str):
        shape = SHAPES[shape]
    b, s = shape.global_batch, shape.seq_len
    stub = cfg.modality != "text"
    if shape.kind == "train":
        inputs = (_sds((b, s, cfg.d_model), cfg.cdtype) if stub
                  else _sds((b, s), jnp.int32))
        return {"inputs": inputs, "labels": _sds((b, s), jnp.int32)}
    if shape.kind == "prefill":
        inputs = (_sds((b, s, cfg.d_model), cfg.cdtype) if stub
                  else _sds((b, s), jnp.int32))
        return {"inputs": inputs}
    # decode: one new token against a seq_len-deep cache
    tokens = (_sds((b, 1, cfg.d_model), cfg.cdtype) if stub
              else _sds((b, 1), jnp.int32))
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, b, shape.seq_len))
    return {"tokens": tokens, "cache": cache}


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocation (dry-run input)."""
    return jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.key(0)))


def build(cfg: ModelConfig, seed: int = 0):
    """Real parameter init (smoke tests / examples)."""
    return transformer.init_params(cfg, jax.random.key(seed))


def build_packed(cfg: ModelConfig, seed: int = 0, *, fuse: bool = True,
                 plan_store=None):
    """``pack_for_inference(cfg, build(cfg, seed), fuse=fuse)`` compiled
    as one program, built so that only the packed tree ever sits in
    device memory.

    Each leaf of the packed tree is made by its own jitted init+pack
    program, from which XLA drops every other leaf's work; a raw weight
    therefore exists only as a temporary of the program that packs it.
    Largest leaves go first, while the device is emptiest.  At
    stablelm-3b's widths the fp32 tree is 10.4 GiB, and raw plus packed
    would not fit a 16 GiB chip.

    ``plan_store`` (a ``gemm.PlanStore``) scopes the pack-time plan
    resolutions as ``Engine(plan_store=)`` does for its own pack, so a
    populated store hands back its pack blocks."""
    def packed_tree():
        return pack_for_inference(
            cfg, transformer.init_params(cfg, jax.random.key(seed)),
            fuse=fuse)

    with gemm_api.use_plan_store(plan_store):
        abstract, treedef = jax.tree.flatten(jax.eval_shape(packed_tree))
        leaves = [None] * len(abstract)
        for i in sorted(range(len(abstract)),
                        key=lambda i: -abstract[i].size
                        * abstract[i].dtype.itemsize):
            leaves[i] = jax.jit(
                lambda i=i: jax.tree.leaves(packed_tree())[i])()
    return jax.tree.unflatten(treedef, leaves)


# ------------------------------------------------- load-time pack (lever 2)
# 2-D projection weights that route through core.panel_gemm when packed.
_PACKABLE = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_dq", "w_uq",
    "w_dkv", "w_kr", "in_proj", "out_proj", "lm_head",
}
# MoE expert banks pack as grouped packs (``packing.pack_grouped``): gate
# and up fused into "wi_gate_up" for the glu epilogue, the down bank as
# "wo"; the serving layer runs them through the grouped plan/execute arm.
# Deliberately unpacked: embed (gather), router (small, fp32), MLA
# absorbed factors w_uk/w_uv (consumed reshaped to (r, H, d) inside the
# einsum, not through `linear`), conv/norm vectors.

# Horizontal fusion groups: same-input projections concatenated along N
# into ONE pack at load (``packing.pack_fused``), so one kernel pass
# streams the shared activations once.  The fused key is what the model
# layers branch on (``attention.gqa_attention``: "wqkv";
# ``transformer._layer_forward``: "w_gate_up"; ``mla_attention``:
# "w_dqkr", or "w_qkr" without q-LoRA).  glu groups combine in the kernel store step, so their pack
# blocks reserve VMEM for the two-accumulator epilogue.
_FUSE_GROUPS = (
    (("wq", "wk", "wv"), "wqkv", None),
    (("w_gate", "w_up"), "w_gate_up", "glu"),
    (("w_dq", "w_dkv", "w_kr"), "w_dqkr", None),
    (("wq", "w_dkv", "w_kr"), "w_qkr", None),
)


# keep_fp32 aliases: user-facing role names -> the param keys they pin.
_KEEP_FP32_ALIASES = {"head": ("lm_head",), "embed": ("embed",)}


def _resolve_keep_fp32(keep_fp32) -> frozenset:
    names: set[str] = set()
    for entry in keep_fp32 or ():
        names.update(_KEEP_FP32_ALIASES.get(entry, (entry,)))
    return frozenset(names)


def pack_for_inference(cfg: ModelConfig, params, *, block_n=None,
                       block_k=None, shardings=None,
                       m_hint: int = PAPER_M, fuse: bool = True,
                       quant: str | None = None,
                       keep_fp32=("head", "embed")) -> dict:
    """Pack every projection weight once at model load (paper §3.2).

    The per-weight (block_n, block_k) decision is the dispatch POLICY's
    (``gemm.pack_blocks``): each weight's (N, K) resolves a plan at
    ``m_hint`` rows (the paper's S = 128 prefill panel), so K >= N
    projections get occupancy-sized fine column panels and N > K
    projections get the deep-K pre-pack blocks.  Explicit ``block_n`` /
    ``block_k`` still override (benchmark sweeps).

    ``fuse=True`` (the default) additionally fuses same-input projection
    groups horizontally (``_FUSE_GROUPS``): Q/K/V (and MLA's three
    down-projections) become one pack with a static split map, and
    gate+up become one pack whose glu combine runs inside the kernel
    store step — the prefill/decode hot paths then emit one GEMM where
    they emitted three.  ``fuse=False`` is the A/B escape hatch
    (``launch/serve.py --no-fusion``).

    ``quant`` ("int8" | "ternary") is the MIXED-PRECISION tree rewrite
    (repro.quant): every packable projection quantizes at pack time —
    fused groups included — EXCEPT the roles named by ``keep_fp32``
    ("head" -> lm_head, "embed" -> the embedding table, or literal
    param names), which keep the fp32 pack.  The default pins the LM
    head and embeddings, the two spots where quantization error lands
    directly on the logits.  Each concrete quantized pack is measured
    and tolerance-gated by the error ledger (docs/quantization.md).

    Stacked per-layer weights (L, K, N) pack along their last two dims;
    the layer scan hands each GEMM the whole stack and the layer's index,
    and the kernel reads that layer's panels in place (``gemm.execute(...,
    layer=)``; quantized packs and grouped banks are sliced).  ``shardings``
    (a matching pytree) re-places each packed array so no resharding
    appears per call.
    """
    keep = _resolve_keep_fp32(keep_fp32)
    if quant is not None:
        from repro.quant.formats import _check_fmt
        _check_fmt(quant)

    def blocks_for(n, k, epilogue=None, fmt=None):
        # explicit overrides keep the legacy fit-to-dim behavior
        bn = packing.fit_block(n, block_n) if block_n else None
        bk = packing.fit_block(k, block_k) if block_k else None
        return gemm_api.pack_blocks(n, k, m_hint=m_hint,
                                    block_n=bn, block_k=bk,
                                    epilogue=epilogue,
                                    weight_format=fmt or "fp32")

    def place_pw(pw, shard_node):
        if shard_node is None:
            return pw
        from repro.quant.formats import SparseTernaryPackedWeight
        if isinstance(pw, SparseTernaryPackedWeight):
            # shardings were derived from the abstract (eval_shape) tree,
            # and abstract packs never compress — the dense-layout specs
            # don't apply to the data-dependent occupied-group slab.
            # Leave the compressed pack unplaced: jit replicates it, and
            # the slab is small by construction (that's the point).
            return pw
        kw = {}
        if isinstance(shard_node, packing.PackedWeight):
            if shard_node.data is not None:
                kw["data"] = jax.device_put(pw.data, shard_node.data)
            scales_s = getattr(shard_node, "scales", None)
            if scales_s is not None and getattr(pw, "scales",
                                                None) is not None:
                kw["scales"] = jax.device_put(pw.scales, scales_s)
        else:
            kw["data"] = jax.device_put(pw.data, shard_node)
        return dataclasses.replace(pw, **kw) if kw else pw

    def pack_one(name, node, shard_node):
        fmt = quant if (quant and name not in keep) else None
        if node.ndim == 3:                          # stacked (L, K, N)
            _, k, n = node.shape
            bn, bk = blocks_for(n, k, fmt=fmt)
            if fmt:
                from repro.quant.formats import quantize_pack
                pw = quantize_pack(node, fmt, block_n=bn, block_k=bk)
            else:
                data = jnp.pad(node,
                               ((0, 0), (0, (-k) % bk), (0, (-n) % bn)))
                pw = packing.PackedWeight(data=data, n=n, k=k,
                                          block_n=bn, block_k=bk)
            return place_pw(pw, shard_node)
        k, n = node.shape
        bn, bk = blocks_for(n, k, fmt=fmt)
        pw = packing.pack(node, block_n=bn, block_k=bk, quant=fmt)
        return place_pw(pw, shard_node)

    def pack_group(group, nodes, shard_node, glu: bool):
        k = nodes[0].shape[-2]
        n_cat = sum(int(w.shape[-1]) for w in nodes)
        # a group quantizes only when every member is quantizable (a
        # keep_fp32 member pins the whole fused pack to fp32)
        fmt = quant if (quant and not any(g in keep for g in group)) \
            else None
        # glu packs budget VMEM for the two-tile/two-accumulator store
        # phase, under the activation the layer will actually execute
        # (vmem_bytes already reserves bias/residual operand headroom
        # unconditionally, so pack-time and execute-time footprints
        # agree whatever else the layer attaches)
        spec = gemm_api.EpilogueSpec(glu=cfg.act) if glu else None
        bn, bk = blocks_for(n_cat, k, epilogue=spec, fmt=fmt)
        pw = packing.pack_fused(list(nodes), block_n=bn, block_k=bk,
                                quant=fmt)
        return place_pw(pw, shard_node)

    def pack_banks(node):
        # the grouped layer's expert banks [.., G, K, N]: blocks as the
        # policy packs one expert's weight; fp32 whatever ``quant`` says
        gate, up, down = node["wi_gate"], node["wi_up"], node["wo"]
        spec = gemm_api.EpilogueSpec(glu=cfg.act)
        bn, bk = blocks_for(2 * gate.shape[-1], gate.shape[-2],
                            epilogue=spec)
        out = {k: v for k, v in node.items()
               if k not in ("wi_gate", "wi_up", "wo")}
        out["wi_gate_up"] = packing.pack_grouped([gate, up], block_n=bn,
                                                 block_k=bk)
        bn, bk = blocks_for(down.shape[-1], down.shape[-2])
        out["wo"] = packing.pack_grouped([down], block_n=bn, block_k=bk)
        return out

    def walk(path, node, shard_node):
        if isinstance(node, dict):
            shard = shard_node if isinstance(shard_node, dict) else {}
            if path and path[-1] == "moe":
                node = pack_banks(node)
            out = {}
            done = set()
            if fuse:
                for group, fused_name, glu in _FUSE_GROUPS:
                    if not all(g in node and hasattr(node[g], "ndim")
                               and node[g].ndim >= 2 for g in group):
                        continue
                    out[fused_name] = pack_group(
                        group, [node[g] for g in group],
                        shard.get(fused_name), glu == "glu")
                    done.update(group)
            for key, v in node.items():
                if key in done:
                    continue
                out[key] = walk(path + (key,), v, shard.get(key))
            return out
        name = path[-1]
        if (isinstance(node, packing.PackedWeight) or name not in _PACKABLE
                or node.ndim < 2):
            return node
        return pack_one(name, node, shard_node)

    return walk((), params, shardings)
