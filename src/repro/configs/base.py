"""Config schema: model architecture + input-shape + run configs."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid
    modality: str = "text"           # text | audio | vlm
    source: str = ""                 # provenance tag from the assignment
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    attention_kind: str = "gqa"      # gqa | mla | none | parallel_ssm
    window: int | None = None        # uniform sliding window (SWA)
    local_global_period: int = 0     # >0: alternate local(window)/global
    logit_softcap: float | None = None
    attn_softcap: float | None = None
    attn_scale: float | None = None
    rope_theta: float = 10000.0
    act: str = "silu"                # silu | gelu
    norm_eps: float = 1e-6
    norm_plus_one: bool = False      # gemma-style (1 + scale) RMSNorm
    post_norms: bool = False         # gemma2 post-attn/post-ffn norms
    embed_scale: bool = False        # gemma: scale embeddings by sqrt(d)
    tie_embeddings: bool = False
    # MLA (deepseek-v3)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0
    kv_latent_norm: bool = False     # RMSNorm on the latent before caching
    # YaRN rope (yarn_factor 0 = plain rope)
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    first_k_dense: int = 0           # leading dense-FFN layers (d_ff wide)
                                     # before the scan of MoE layers
    # MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    norm_topk_prob: bool = True      # renormalise the top-k weights
    routed_scaling: float = 1.0      # times the top-k weights
    held_experts: tuple = ()         # (lo, hi): the routed experts this
                                     # chip holds; () = all of them
    capacity_factor: float = 1.25
    moe_dispatch_groups: int = 0     # 0/1 = global cumsum; launchers set
                                     # this to the batch-shard count so
                                     # dispatch never crosses a shard
                                     # (§Perf cell 3)
    moe_dispatch: str = "grouped"    # grouped | global — offline-sweep
                                     # pick per arch (§Perf D2: 7.3x win
                                     # on qwen3; measured regression on
                                     # deepseek-v3, which keeps global)
    # SSM (mamba2 / hybrid)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    conv_width: int = 4
    # dtypes / training
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = True
    remat_policy: str = "nothing"    # nothing | dots (dots saveable —
                                     # required under manual_dp: the
                                     # nothing_saveable policy trips an
                                     # XLA CHECK inside partial-auto
                                     # shard_map at high partition counts)
    optimizer: str = "adamw"         # adamw | adafactor
    # serving
    cache_kind: str = "auto"         # auto | full | window
    cache_dtype: str = "bfloat16"

    @property
    def pdtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return _DTYPES[self.compute_dtype]

    @property
    def held_range(self) -> tuple[int, int]:
        """(lo, hi) of the routed experts this chip holds."""
        return tuple(self.held_experts) or (0, self.num_experts)

    @property
    def is_windowed_only(self) -> bool:
        """True iff every attention layer is windowed (ring cache legal)."""
        return (self.window is not None and self.local_global_period == 0
                and self.attention_kind in ("gqa", "parallel_ssm"))

    @property
    def resolved_cache_kind(self) -> str:
        if self.cache_kind != "auto":
            return self.cache_kind
        return "window" if self.is_windowed_only else "full"

    def param_count(self) -> int:
        """Analytic parameter count (embedding + layers + head)."""
        d, v, lyr = self.d_model, self.vocab_size, self.num_layers
        n = v * d                                     # embed
        if not self.tie_embeddings:
            n += v * d                                # lm head
        per = 2 * d                                   # 2 norms
        if self.post_norms:
            per += 2 * d
        if self.attention_kind == "gqa" or self.attention_kind == "parallel_ssm":
            per += d * self.num_heads * self.head_dim * 2  # wq, wo
            per += d * self.num_kv_heads * self.head_dim * 2
        if self.attention_kind == "mla":
            q_in = self.q_lora_rank or d
            per += d * self.q_lora_rank
            per += q_in * self.num_heads * (
                self.qk_nope_dim + self.qk_rope_dim)
            per += d * (self.kv_lora_rank + self.qk_rope_dim)
            per += self.kv_lora_rank * self.num_heads * (
                self.qk_nope_dim + self.v_head_dim)
            per += self.num_heads * self.v_head_dim * d
        if self.attention_kind in ("none", "parallel_ssm"):
            d_in = self.ssm_heads * self.ssm_head_dim
            gn = self.ssm_groups * self.ssm_state
            per += d * (2 * d_in + 2 * gn + self.ssm_heads)
            per += d_in * d + d_in
        if self.kv_latent_norm:
            per += self.kv_lora_rank
        dense_ffn = d * self.d_ff * 3 if self.d_ff else 0
        if self.family != "moe":
            return n + lyr * (per + dense_ffn)
        lo, hi = self.held_range
        moe = d * self.num_experts                     # router
        moe += (hi - lo) * d * self.moe_d_ff * 3
        moe += self.num_shared_experts * d * self.moe_d_ff * 3
        k = self.first_k_dense
        return n + lyr * per + k * dense_ffn + (lyr - k) * moe

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k experts)."""
        if self.family != "moe":
            return self.param_count()
        lo, hi = self.held_range
        inactive = (hi - lo - min(self.experts_per_token, hi - lo)) \
            * self.d_model * self.moe_d_ff * 3 \
            * (self.num_layers - self.first_k_dense)
        return self.param_count() - inactive


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str                        # train_4k | prefill_32k | ...
    kind: str                        # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatch_per_device: int = 1   # grad-accum: global_batch /
                                     # (data_shards * microbatch)
    learning_rate: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    seed: int = 0
    grad_compression: str = "none"   # none | bf16 (wire dtype of grad sync)
    shard_grad_accum: bool = True    # FSDP grad accumulators (§Perf it. 1)
    gather_params_once: bool = False # hoist FSDP all-gather out of the
                                     # microbatch loop (§Perf it. 3; costs
                                     # full-d params resident per device)
    manual_dp: bool = False          # shard_map manual data axis: local
                                     # grad accum, ONE sync/step (§Perf 4)
