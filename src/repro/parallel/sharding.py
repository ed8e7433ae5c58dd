"""Sharding rules: logical parameter/activation axes → mesh axes.

Posture (DESIGN.md §4): batch→(pod, data); tensor-parallel dims
(vocab / flattened heads / d_ff / experts / ssm_inner)→model; parameter
d_model dims→data (**FSDP** — params and optimizer state are sharded over
the data axis and all-gathered per layer inside the scan, which is what
fits deepseek-v3-671b in 16 GB/chip).  The `pod` axis composes with `data`
for the batch only, so weights replicate across pods and the only
cross-pod (DCN) collective in a train step is the gradient all-reduce.

Every rule is divisibility-guarded: a dim that a mesh axis does not divide
falls back to replication on that dim (e.g. hymba's 25 heads — the
flattened 25*64=1600 projection dim shards; the (B,S,25,64) activation
does not, and GSPMD inserts the resharding, which the dry-run's collective
parse then prices).  This mirrors production logical-axis-rule systems
(MaxText et al.) rather than hand-placing every array.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Activation logical axes → mesh axes (used by activation_sharder).
ACT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "d_model": None,          # activations replicate d_model (params don't)
    "vocab": "model",
    "heads": "model",
    "kv_seq": None,
    "expert_group": ("pod", "data"),   # MoE dispatch groups ≙ batch shards
    "experts": "model",                # EP: buffers redistribute via a2a
}

# Parameter-name → PartitionSpec for the per-layer array (the leading
# stacked-layer dim, when present, is prepended as None automatically).
# Specs may name axes a given dim cannot host; the divisibility guard
# drops them per-array.
PARAM_RULES = {
    # embeddings / head
    "embed": P("model", "data"),          # (vocab, d_model)
    "lm_head": P("data", "model"),        # (d_model, vocab)
    # attention (flattened projections)
    "wq": P("data", "model"),
    "wk": P("data", "model"),
    "wv": P("data", "model"),
    "wqkv": P("data", "model"),           # fused [K, Nq+Nk+Nv] pack
    "wo": P("model", "data"),
    # MLA
    "w_dq": P("data", "model"),
    "w_uq": P("data", "model"),           # (q_lora, h*(nope+rope))
    "w_dkv": P("data", "model"),
    "w_kr": P("data", "model"),
    "w_uk": P("data", "model"),           # (kv_lora, h*nope)
    "w_uv": P("data", "model"),
    # dense / shared-expert FFN
    "w_gate": P("data", "model"),
    "w_up": P("data", "model"),
    "w_gate_up": P("data", "model"),      # fused [K, 2F] glu pack
    "w_down": P("model", "data"),
    # MLA fused down-projections [K, q_lora + kv_lora + rope]
    "w_dqkr": P("data", "model"),
    # MLA without q-LoRA: fused W_q | W_kv_a [K, H*(nope+rope) + r + rope]
    "w_qkr": P("data", "model"),
    # MoE (EP: experts over model)
    "router": P("data", None),
    "wi_gate": P("model", "data", None),  # (E, d, f)
    "wi_up": P("model", "data", None),
    "wi_gate_up": P("model", "data", None),  # grouped glu pack (E, d, 2f)
    # mamba
    "in_proj": P("data", "model"),
    "conv_w": P(None, "model"),
    "out_proj": P("model", "data"),
}
# moe down-proj shares the "wo" key inside p["moe"]; disambiguated by rank.
_MOE_WO = P("model", None, "data")

_VEC_KEYS = {  # 1-D per-layer vectors: replicate
    "ln1", "ln2", "ln1_post", "ln2_post", "final_norm", "norm",
    "ln_attn_out", "ln_ssm_out", "a_log", "d_skip", "dt_bias", "conv_b",
    "kv_norm",
}


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        return math.prod(axis_size(mesh, n) for n in name)
    return mesh.shape[name] if name in mesh.shape else 1


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes that do not divide their dim (replicate fallback).

    For composite entries like ("pod", "data"), keeps the longest prefix
    whose product divides the dim.
    """
    out = []
    for d, entry in enumerate(spec):
        if entry is None or d >= len(shape):
            out.append(None)
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        names = [n for n in names if n in mesh.shape]
        kept = []
        prod = 1
        for n in names:
            if shape[d] % (prod * mesh.shape[n]) == 0:
                kept.append(n)
                prod *= mesh.shape[n]
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept
                                                      else None))
    out += [None] * (len(shape) - len(out))
    return P(*out)


def _path_names(path) -> list[str]:
    out = []
    for e in path:
        for attr in ("key", "name", "idx"):
            if hasattr(e, attr):
                out.append(str(getattr(e, attr)))
                break
        else:
            out.append(str(e))
    return out


def _spec_for(names: list[str], shape, mesh: Mesh, stacked: bool) -> P:
    # Rule lookup: last path component with a rule (so PackedWeight.data
    # under "wq" resolves to the "wq" rule).
    rule_name = next((n for n in reversed(names)
                      if n in PARAM_RULES or n in _VEC_KEYS), None)
    core_ndim = len(shape) - (1 if stacked else 0)
    if rule_name in _VEC_KEYS or rule_name is None or core_ndim <= 1:
        return fit_spec(P(*([None] * len(shape))), shape, mesh)
    if rule_name == "wo" and "moe" in names:
        base = _MOE_WO
    else:
        base = PARAM_RULES[rule_name]
    if stacked:
        base = P(None, *base)
    return fit_spec(base, shape, mesh)


def param_specs(params_tree, mesh: Mesh):
    """PartitionSpec pytree for a params pytree (arrays, ShapeDtypeStructs,
    or PackedWeight leaves).  Arrays under params["layers"] are
    scan-stacked (leading L dim → None)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
    specs = []
    for path, leaf in flat:
        names = _path_names(path)
        stacked = ("layers" in names or "dense_layers" in names) \
            and hasattr(leaf, "ndim") and leaf.ndim >= 2
        # PackedWeight static fields (ints) flatten away; leaves here are
        # arrays / ShapeDtypeStructs only.
        specs.append(_spec_for(names, leaf.shape, mesh, stacked))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_tree, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def serve_param_specs(params_tree, mesh: Mesh, *,
                      hbm_budget: int = 16 * 2**30,
                      reserve_fraction: float = 0.5):
    """Serving placement — §Perf iteration C1.

    FSDP (d_model over data) is an OPTIMIZER-state compromise; at
    inference there is no optimizer state, and keeping it makes every
    decode step all-gather the weights (measured: the dominant collective
    on every decode cell).  Deployment rule: if TP-only weights fit in
    ``reserve_fraction`` of HBM (rest reserved for KV cache +
    activations), replicate over the data axes; otherwise keep the FSDP
    specs (deepseek-v3-671b: 84 GB/chip TP-only — stays sharded).

    This is the paper's lever-2 thinking applied to placement: pay once
    at model load (more resident bytes) to delete per-call work (the
    gather) — exactly the pre-pack trade.
    """
    specs = param_specs(params_tree, mesh)

    def drop_data(spec):
        def keep(entry):
            if entry is None:
                return None
            names = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(n for n in names if n not in ("data", "pod"))
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return P(*(keep(e) for e in spec))

    replicated = jax.tree.map(drop_data, specs,
                              is_leaf=lambda x: isinstance(x, P))
    # per-device bytes under the replicated plan
    leaves = jax.tree_util.tree_flatten(params_tree)[0]
    spec_leaves = jax.tree.leaves(replicated,
                                  is_leaf=lambda x: isinstance(x, P))
    per_dev = 0
    for leaf, spec in zip(leaves, spec_leaves):
        shards = 1
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            shards *= math.prod(mesh.shape[n] for n in names)
        per_dev += (math.prod(leaf.shape)
                    * np.dtype(leaf.dtype).itemsize) // max(shards, 1)
    if per_dev <= hbm_budget * reserve_fraction:
        return replicated
    return specs


def serve_param_shardings(params_tree, mesh: Mesh, **kw):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        serve_param_specs(params_tree, mesh, **kw),
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------ cache
def cache_specs(cache_tree, mesh: Mesh, cfg=None):
    """Specs for a decode cache pytree (layer-stacked leading dim).

    k/v: (L, B, T, Hkv, D) — batch over (pod, data); kv_heads over model
    when divisible, else head_dim over model (DESIGN.md §4).  SSM state:
    (L, B, H, P, N) — heads over model when divisible, else head_dim.
    """
    def spec(path, aval):
        name = path[-1]
        shape = aval.shape
        if name == "index":
            return P()
        if name in ("k", "v"):
            base = P(None, ("pod", "data"), None, "model", None)
            if shape[3] % max(axis_size(mesh, "model"), 1) != 0:
                base = P(None, ("pod", "data"), None, None, "model")
        elif name == "pos":
            base = P(None, ("pod", "data"), None)
        elif name in ("ckv", "krope"):                 # MLA latent cache
            base = P(None, ("pod", "data"), None, None)
        elif name == "state":
            base = P(None, ("pod", "data"), "model", None, None)
            if shape[2] % max(axis_size(mesh, "model"), 1) != 0:
                base = P(None, ("pod", "data"), None, "model", None)
        elif name == "conv":
            base = P(None, ("pod", "data"), None, "model")
        else:
            base = P(*([None] * len(shape)))
        return fit_spec(base, shape, mesh)

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        return spec(path, node)
    return walk((), cache_tree)


def cache_shardings(cache_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_specs(cache_tree, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------------------- activations
def batch_spec(batch_size: int, mesh: Mesh, extra_dims: int = 1) -> P:
    """Input-batch spec: largest (pod, data) prefix dividing batch_size."""
    return fit_spec(P(("pod", "data"), *([None] * extra_dims)),
                    (batch_size,) + (1,) * extra_dims, mesh)


def activation_sharder(mesh: Mesh, *, drop_axes: frozenset = frozenset()):
    """shard(x, *logical_names) → with_sharding_constraint under `mesh`.

    ``drop_axes``: mesh axes to omit from every constraint — used inside
    partial-manual shard_map regions, where the manual axes (data/pod)
    must not appear in auto sharding constraints.
    """
    def _filter(entry):
        if entry is None:
            return None
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(n for n in names if n not in drop_axes)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    def shard(x, *names):
        spec = fit_spec(P(*(_filter(ACT_RULES.get(n)) for n in names)),
                        x.shape, mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return shard


def count_shards(tree, mesh: Mesh) -> dict:
    """Diagnostics: bytes per device under the computed shardings."""
    specs = param_specs(tree, mesh)
    total = 0
    per_dev = 0
    for aval, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(specs, is_leaf=lambda x:
                                          isinstance(x, P))):
        nbytes = math.prod(aval.shape) * np.dtype(aval.dtype).itemsize
        shards = math.prod(axis_size(mesh, e) for e in spec
                           if e is not None)
        total += nbytes
        per_dev += nbytes // max(shards, 1)
    return {"global_bytes": total, "bytes_per_device": per_dev}
