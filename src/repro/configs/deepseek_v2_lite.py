"""deepseek-v2-lite [moe]: MLA without q-LoRA + 64-expert top-6 MoE.

27L d_model=2048 16H vocab=102400; layer 0 dense (d_ff 10944), layers
1-26 MoE: 64 routed experts of width 1408, top-6 softmax greedy with the
top-6 weights left unnormalised, plus 2 shared experts (one SwiGLU of
width 2816).  MLA: W_q straight from x (no q-LoRA), a 512-d latent with
its own RMSNorm and a 64-d rope key; YaRN rope (factor 40 over 4096
original positions, mscale = mscale_all_dim = 0.707, so the softmax
scale is 192^-0.5 * 1.2608^2 and cos/sin are unscaled)
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

Rope runs on the repo's half-split layout; the release's interleaved
layout is the same map under a fixed permutation of W_q's and W_kv_a's
rope columns, so with random weights the two are the same model.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # q-head count; MLA caches the shared latent
    head_dim=192,              # qk_nope + qk_rope
    d_ff=10944,                # the leading dense layer's width
    vocab_size=102400,
    attention_kind="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    kv_latent_norm=True,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    first_k_dense=1,
    num_experts=64,
    num_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    routed_scaling=1.0,
    rope_theta=10000.0,
    norm_eps=1e-6,
    compute_dtype="bfloat16",
)
