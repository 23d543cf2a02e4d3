"""DeepSeek-V2-Lite [MLA, DeepSeekMoE 64 routed + 2 shared, top-6;
hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512, no
query compression, qk_nope 128 + qk_rope 64, v 128) with YaRN rope
(factor 40 over 4,096 positions), layer 0 a dense SwiGLU of width 10,944,
layers 1-26 routed experts of width 1,408 (64, top-6 softmax gates NOT
renormalized, routed_scaling_factor 1.0 — a no-op left out) beside 2
shared experts (one SwiGLU of width 2,816), vocab 102,400, untied head,
RMSNorm eps 1e-6.  Equations: DeepSeek-V2, arXiv:2405.04434, Sec. 2.1-2.2.

The rope dims use the program's half-split layout, where DeepSeek's
checkpoint pairs interleaved lanes: the two are equal under a fixed
permutation of the rope columns of W_q and W_kv_a."""

import dataclasses

from ..models.config import ModelConfig, Yarn

CONFIG = ModelConfig(
    arch="deepseek_v2_lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    kv_heads=16,
    d_ff=1408,
    vocab=102400,
    head_dim=192,
    rope_theta=10000.0,
    n_experts=64,
    top_k=6,
    moe_dispatch="dropless",
    norm_topk_prob=False,
    n_shared_experts=2,
    first_dense_layers=1,
    dense_d_ff=10944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, kv_heads=4, d_ff=32,
    vocab=512, n_experts=8, top_k=2, head_dim=24, dense_d_ff=96,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    yarn=Yarn(factor=40.0, original_max_position=64, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
)
